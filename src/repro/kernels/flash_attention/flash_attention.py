"""FlashAttention-2-style Pallas TPU kernel.

Adaptation notes (paper -> TPU):
  The paper applies Flash Attention (Dao et al.) as *the* state-of-the-art
  optimization for TTI/TTV attention.  On GPU the win is HBM<->SRAM traffic;
  on TPU the analogous hierarchy is HBM<->VMEM.  This kernel tiles Q into
  ``block_q`` x D blocks resident in VMEM, streams K/V in ``block_kv`` x D
  blocks, and keeps the online-softmax running statistics (m, l) plus the
  fp32 output accumulator in VMEM scratch.  Block sizes default to multiples
  of the 128-lane VREG / 128x128 MXU geometry.

  Grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the last axis is the
  sequential reduction axis — Pallas TPU executes it in order, so scratch
  carries across ``ikv`` steps and the output block is written once at the
  final step.  Causal / local-window blocks that are fully masked are skipped
  with ``pl.when`` (they still occupy a grid step but do no FLOPs / loads).

Layout: q (B, H, Sq, D); k/v (B, KVH, Skv, D); out (B, H, Sq, D).
GQA is handled in the K/V index_map (kv head = q head // group) — no
materialized ``repeat`` ever hits HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT

NEG_INF = -1e30
# Lane width of the VPU; scalar-per-row scratch is stored broadcast over one
# 128-lane vector so it maps onto native VREG tiles.
_LANES = 128


def _fa_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_kv: int,
    sq_valid: int,
    skv_valid: int,
    num_kv_blocks: int,
    kv_offset: int,
):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- block-level skip conditions (no loads / FLOPs for masked blocks) ---
    q_lo = iq * block_q + kv_offset  # absolute position of first query row
    q_hi = q_lo + block_q - 1
    kv_lo = ikv * block_kv
    kv_hi = kv_lo + block_kv - 1
    should = kv_lo < skv_valid  # skip padded tail of K/V
    if causal:
        should = jnp.logical_and(should, q_hi >= kv_lo)
    if window is not None:
        should = jnp.logical_and(should, q_lo - kv_hi < window)

    @pl.when(should)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bkv, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # (bq, bkv)

        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        cols = kv_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        ok = cols < skv_valid
        if causal:
            ok = jnp.logical_and(ok, cols <= rows)
        if window is not None:
            ok = jnp.logical_and(ok, rows - cols < window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ikv == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding) -> 0 output
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, D)   Sq divisible by block_q (pre-padded)
    k: jax.Array,  # (B, KVH, Skv, D) Skv divisible by block_kv
    v: jax.Array,
    *,
    scale: float,
    causal: bool = False,
    window: int | None = None,
    sq_valid: int | None = None,
    skv_valid: int | None = None,
    kv_offset: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    _, KVH, Skv, _ = k.shape
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    nq = Sq // block_q
    nkv = Skv // block_kv
    sq_valid = Sq if sq_valid is None else sq_valid
    skv_valid = Skv if skv_valid is None else skv_valid

    kernel = functools.partial(
        _fa_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        sq_valid=sq_valid,
        skv_valid=skv_valid,
        num_kv_blocks=nkv,
        kv_offset=kv_offset,
    )

    grid = (B, H, nq, nkv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, D),
                lambda b, h, iq, ikv, group=group: (b, h // group, ikv, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, D),
                lambda b, h, iq, ikv, group=group: (b, h // group, ikv, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, D), jnp.float32),  # acc
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Temporal attention (TTV, paper §VI) with the layout permute fused into the
# BlockSpec index_map.
# ---------------------------------------------------------------------------


def _temporal_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                     frames_valid: int, heads: int, head_dim: int):
    # Blocks arrive as (1, F, HWB, heads * head_dim): frames x spatial block
    # x the lanes of ``heads`` heads, straight from the (B, F, HW, H*D)
    # view of the UNet's spatial layout.
    q, k, v = (jnp.swapaxes(r[0].astype(jnp.float32), 0, 1)
               for r in (q_ref, k_ref, v_ref))  # (N, F, L)
    F = q.shape[1]

    # Batched over the spatial axis N: each spatial position attends across
    # frames — an (F x D) @ (D x F) MXU op per spatial lane, tiny matmul
    # dims (F ~ 8..64) with a large batch, which is exactly the
    # low-utilization regime the paper measures on GPU.  The fused
    # index_map means the (B,F,HW,H,D) tensor is *never* permuted in HBM.
    # Heads share the lane axis; head h is selected with a lane mask (a
    # lane slice at an offset that is not a multiple of 128 does not lower).
    head = jax.lax.broadcasted_iota(jnp.int32, (1, 1, q.shape[2]), 2) // head_dim
    out = jnp.zeros_like(q)
    for h in range(heads):
        mh = head == h
        s = jnp.einsum("nfl,ngl->nfg", jnp.where(mh, q, 0.0), k,
                       preferred_element_type=jnp.float32) * scale
        if frames_valid < F:
            g = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(g < frames_valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        out = out + jnp.einsum("nfg,ngl->nfl", p, jnp.where(mh, v, 0.0),
                               preferred_element_type=jnp.float32)
    o_ref[0] = jnp.swapaxes(out, 0, 1).astype(o_ref.dtype)


def temporal_flash_attention(
    q: jax.Array,  # (B, F, HW, H, D) — spatial layout straight from the UNet
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    block_hw: int = 128,
    frames_valid: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, F, HW, H, D = q.shape
    block_hw = min(block_hw, HW)
    assert HW % block_hw == 0, (HW, block_hw)
    n_hw = HW // block_hw
    frames_valid = F if frames_valid is None else frames_valid
    # Heads per block: the fewest whose lanes fill whole 128-lane vregs,
    # else all of them (the full lane dim).
    hb = next((n for n in range(1, H + 1)
               if H % n == 0 and (n * D) % 128 == 0), H)

    kernel = functools.partial(
        _temporal_kernel, scale=scale, frames_valid=frames_valid, heads=hb,
        head_dim=D,
    )
    spec = pl.BlockSpec((1, F, block_hw, hb * D),
                        lambda b, h, ihw: (b, 0, ihw, h))
    flat = lambda t: t.reshape(B, F, HW, H * D)
    out = pl.pallas_call(
        kernel,
        grid=(B, H // hb, n_hw),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, F, HW, H * D), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="temporal_attention",
        interpret=interpret,
    )(flat(q), flat(k), flat(v))
    return out.reshape(B, F, HW, H, D)
