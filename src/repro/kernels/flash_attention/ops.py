"""Dispatching wrappers around the flash-attention kernel.

``attention(...)`` is the single call-site API used by every model in the
framework.  Implementations:

  * ``pallas``      — the Pallas TPU kernel (TARGET hardware path).
  * ``interpret``   — same kernel body, interpreter mode (CPU validation).
  * ``blocked_jax`` — pure-``lax.scan`` flash algorithm: identical asymptotic
                      HBM traffic (no N^2 materialization), differentiable,
                      lowers on any backend.  Used for training and for the
                      CPU-backend multi-pod dry-run (Pallas TPU kernels cannot
                      lower for the CPU target).
  * ``naive``       — materializes the (Sq, Skv) similarity matrix.  Kept
                      deliberately: it is the paper's "Baseline Attention"
                      against which Flash Attention is characterized (Fig. 6,
                      Table II).
  * ``auto``        — pallas on TPU, blocked_jax elsewhere.

Shapes: q (B, Sq, H, D); k/v (B, Skv, KVH, D); out (B, Sq, H, D).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_bhsd,
    temporal_flash_attention,
)

Impl = Literal["auto", "pallas", "interpret", "blocked_jax", "naive"]

NEG_INF = -1e30


def _resolve(impl: Impl) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "blocked_jax"
    return impl


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    kv_offset: int = 0,
    kv_len: jax.Array | None = None,
    impl: Impl = "auto",
    block_q: int = 512,
    block_kv: int = 512,
) -> jax.Array:
    """Multi-head (GQA) attention with selectable implementation."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    scale = scale if scale is not None else D**-0.5
    impl = _resolve(impl)

    if impl == "naive":
        return _ref.attention_ref(
            q, k, v, causal=causal, window=window, scale=scale,
            kv_offset=kv_offset, kv_len=kv_len,
        )
    if impl == "blocked_jax":
        return _blocked_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            kv_offset=kv_offset, kv_len=kv_len,
            block_q=block_q, block_kv=block_kv,
        )
    if impl in ("pallas", "interpret"):
        if kv_len is not None:
            raise NotImplementedError(
                "dynamic kv_len is served by the decode path (decode_attention), "
                "not the prefill kernel"
            )
        # (B, S, H, D) -> (B, H, S, D), pad sequence dims to block multiples.
        qt = _pad_to(q.transpose(0, 2, 1, 3), 2, min(block_q, _round_block(Sq)))
        kt = _pad_to(k.transpose(0, 2, 1, 3), 2, min(block_kv, _round_block(Skv)))
        vt = _pad_to(v.transpose(0, 2, 1, 3), 2, min(block_kv, _round_block(Skv)))
        from repro.parallel.sharding import kernel_on_mesh

        out = kernel_on_mesh(
            functools.partial(
                flash_attention_bhsd,
                scale=scale, causal=causal, window=window,
                sq_valid=Sq, skv_valid=Skv, kv_offset=kv_offset,
                block_q=min(block_q, qt.shape[2]),
                block_kv=min(block_kv, kt.shape[2]),
                interpret=(impl == "interpret")),
            (qt, kt, vt), (True, True, True))
        return out[:, :, :Sq, :].transpose(0, 2, 1, 3)
    raise ValueError(f"unknown impl {impl!r}")


def _round_block(s: int) -> int:
    """Smallest power-of-two-ish block >= 128 that keeps padding waste low."""
    b = 128
    while b < s and b < 512:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# blocked_jax: the flash algorithm in pure lax.scan
# ---------------------------------------------------------------------------


def _blocked_attention(
    q, k, v, *, causal, window, scale, kv_offset, kv_len, block_q, block_kv
):
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    group = H // KVH
    bq = min(block_q, max(128, Sq))
    bkv = min(block_kv, max(128, Skv))

    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bkv)
    vp = _pad_to(v, 1, bkv)
    nq = qp.shape[1] // bq
    nkv = kp.shape[1] // bkv

    # (nq, B, bq, KVH, group, D)
    q_blocks = qp.reshape(B, nq, bq, KVH, group, D).transpose(1, 0, 2, 3, 4, 5)
    # (nkv, B, bkv, KVH, D)
    k_blocks = kp.reshape(B, nkv, bkv, KVH, D).transpose(1, 0, 2, 3, 4)
    v_blocks = vp.reshape(B, nkv, bkv, KVH, D).transpose(1, 0, 2, 3, 4)

    # Megatron-style head-parallel attention: after the GQA reshape the query
    # head axis is split (KVH, group); pin the group axis to the TP mesh axis
    # so the SPMD partitioner keeps scores/accumulators head-sharded instead
    # of replicating them (K/V stay replicated across the group — correct
    # and cheap for GQA where KVH < TP width).
    from repro.parallel.sharding import constrain

    q_blocks = constrain(q_blocks, (None, "batch", None, None, "model", None))
    k_blocks = constrain(k_blocks, (None, "batch", None, None, None))
    v_blocks = constrain(v_blocks, (None, "batch", None, None, None))

    def q_block_step(iq, qb):
        qf = qb.astype(jnp.float32)

        def kv_step(carry, inp):
            m_prev, l_prev, acc = carry
            ikv, kb, vb = inp
            kf = kb.astype(jnp.float32)
            vf = vb.astype(jnp.float32)
            # s: (B, KVH, group, bq, bkv)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
            rows = kv_offset + iq * bq + jnp.arange(bq)[:, None]
            cols = ikv * bkv + jnp.arange(bkv)[None, :]
            ok = cols < Skv
            if causal:
                ok = jnp.logical_and(ok, cols <= rows)
            if window is not None:
                ok = jnp.logical_and(ok, rows - cols < window)
            ok = jnp.broadcast_to(ok[None, None, None], s.shape)
            if kv_len is not None:
                valid = cols[None, None, None] < kv_len[:, None, None, None, None]
                ok = jnp.logical_and(ok, valid)
            s = jnp.where(ok, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            # alpha: (B, KVH, group, bq, 1) -> align to acc (B, bq, KVH, group, D)
            alpha_t = alpha[..., 0].transpose(0, 3, 1, 2)[..., None]
            acc_new = acc * alpha_t + jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, group, bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, group, bq, 1), jnp.float32)
        acc0 = jnp.zeros((B, bq, KVH, group, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, acc0), (jnp.arange(nkv), k_blocks, v_blocks)
        )
        l = jnp.where(l == 0.0, 1.0, l)
        # l: (B, KVH, group, bq, 1) -> align to acc (B, bq, KVH, group, D)
        l_t = l[..., 0].transpose(0, 3, 1, 2)[..., None]
        return (acc / l_t).astype(q.dtype)

    out_blocks = jax.lax.map(
        lambda args: q_block_step(*args), (jnp.arange(nq), q_blocks)
    )
    out = out_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * bq, H, D)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# Decode attention (single new token against a KV cache).
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KVH, D)
    v_cache: jax.Array,
    *,
    kv_len: jax.Array,  # (B,) valid lengths
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Decode-phase attention: the paper's Table III 'Decode' regime.

    The (1, S) score row is tiny; the cost is streaming the cache from HBM —
    the memory-bound regime in which the paper finds Flash Attention gives
    little benefit.  We therefore use a plain jnp implementation (XLA already
    streams the cache optimally); the distributed seq-sharded variant lives
    in ``repro.parallel.decode_shard``.
    """
    B, _, H, D = q.shape
    _, S, KVH, _ = k_cache.shape
    group = H // KVH
    scale = scale if scale is not None else D**-0.5
    qf = q.astype(jnp.float32).reshape(B, KVH, group, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qf, kf) * scale
    pos = jnp.arange(S)[None, None, None, :]
    ok = pos < kv_len[:, None, None, None]
    if window is not None:
        ok = jnp.logical_and(ok, pos >= (kv_len[:, None, None, None] - window))
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, vf)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Temporal attention dispatch (paper §VI)
# ---------------------------------------------------------------------------


def temporal_attention(
    x_q: jax.Array,  # (B, F, HW, H, D) spatial layout
    x_k: jax.Array,
    x_v: jax.Array,
    *,
    scale: float | None = None,
    impl: Impl = "auto",
    block_hw: int = 128,
) -> jax.Array:
    """Attention across the frame axis, without materializing the transpose.

    ``pallas``/``interpret`` use the fused-layout kernel; ``blocked_jax`` and
    ``naive`` fall back to permute + standard attention (the conventional GPU
    implementation the paper profiles).
    """
    B, F, HW, H, D = x_q.shape
    scale = scale if scale is not None else D**-0.5
    impl = _resolve(impl)
    if impl in ("pallas", "interpret"):
        hw_pad = (-HW) % min(block_hw, HW)
        if hw_pad:
            pads = [(0, 0), (0, 0), (0, hw_pad), (0, 0), (0, 0)]
            x_q, x_k, x_v = (jnp.pad(t, pads) for t in (x_q, x_k, x_v))
        from repro.parallel.sharding import kernel_on_mesh

        out = kernel_on_mesh(
            functools.partial(
                temporal_flash_attention, scale=scale,
                block_hw=min(block_hw, x_q.shape[2]),
                interpret=(impl == "interpret")),
            (x_q, x_k, x_v), (True, True, True))
        return out[:, :, :HW]
    # Conventional path: materialized permute, then standard attention over F.
    perm = lambda t: t.transpose(0, 2, 1, 3, 4).reshape(B * HW, F, H, D)
    out = attention(
        perm(x_q), perm(x_k), perm(x_v), causal=False, scale=scale, impl=impl,
        block_q=max(128, F), block_kv=max(128, F),
    )
    return out.reshape(B, HW, F, H, D).transpose(0, 2, 1, 3, 4)
