"""Per-kernel correctness: shape/dtype sweeps against the pure-jnp oracles.

Pallas kernels run in interpret mode (CPU executes the kernel body); every
other impl is swept too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.conv2d import ref as conv_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.groupnorm_silu import ops as gn_ops
from repro.kernels.groupnorm_silu import ref as gn_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


ATTN_SHAPES = [
    # B, Sq, Skv, H, KVH, D
    (1, 128, 128, 4, 4, 64),
    (2, 200, 200, 8, 2, 64),   # GQA + non-multiple seq
    (1, 257, 257, 4, 1, 128),  # MQA, prime-ish seq
    (2, 64, 512, 4, 4, 32),    # cross-attention (Skv != Sq)
]


@pytest.mark.parametrize("impl", ["interpret", "blocked_jax"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", ATTN_SHAPES)
def test_attention_matches_oracle(impl, dtype, shape):
    B, Sq, Skv, H, KVH, D = shape
    key = jax.random.PRNGKey(42)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, D), dtype)
    k = jax.random.normal(kk, (B, Skv, KVH, D), dtype)
    v = jax.random.normal(kv, (B, Skv, KVH, D), dtype)
    causal = Sq == Skv
    gold = fa_ref.attention_ref(q, k, v, causal=causal)
    out = fa_ops.attention(q, k, v, causal=causal, impl=impl,
                           block_q=128, block_kv=128)
    np.testing.assert_allclose(
        out.astype(jnp.float32), gold.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("impl", ["interpret", "blocked_jax"])
@pytest.mark.parametrize("window", [16, 64])
def test_attention_local_window(impl, window):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 150, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 150, 4, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 150, 4, 32))
    gold = fa_ref.attention_ref(q, k, v, causal=True, window=window)
    out = fa_ops.attention(q, k, v, causal=True, window=window, impl=impl,
                           block_q=128, block_kv=128)
    np.testing.assert_allclose(out, gold, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["interpret", "blocked_jax"])
@pytest.mark.parametrize("F,HW", [(4, 64), (8, 100), (16, 32)])
def test_temporal_attention_fused_layout(impl, F, HW):
    key = jax.random.PRNGKey(7)
    shape = (2, F, HW, 4, 32)
    xq = jax.random.normal(key, shape)
    xk = jax.random.normal(jax.random.fold_in(key, 1), shape)
    xv = jax.random.normal(jax.random.fold_in(key, 2), shape)
    gold = fa_ref.temporal_attention_ref(xq, xk, xv)
    out = fa_ops.temporal_attention(xq, xk, xv, impl=impl, block_hw=32)
    np.testing.assert_allclose(out, gold, rtol=3e-5, atol=3e-5)


def test_decode_attention_matches_masked_ref():
    key = jax.random.PRNGKey(3)
    B, S, H, KVH, D = 3, 64, 8, 2, 32
    q = jax.random.normal(key, (B, 1, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KVH, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KVH, D))
    kv_len = jnp.array([5, 64, 33])
    gold = fa_ref.attention_ref(q, k, v, kv_len=kv_len)
    out = fa_ops.decode_attention(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(out, gold, rtol=2e-5, atol=2e-5)


def test_blocked_attention_grad_matches_naive():
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (1, 96, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 96, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 96, 2, 16))

    def loss(fn):
        return lambda q: (fn(q) ** 2).sum()

    g_naive = jax.grad(loss(lambda q: fa_ops.attention(
        q, k, v, causal=True, impl="naive")))(q)
    g_blocked = jax.grad(loss(lambda q: fa_ops.attention(
        q, k, v, causal=True, impl="blocked_jax", block_q=32, block_kv=32)))(q)
    np.testing.assert_allclose(g_blocked, g_naive, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused implicit-GEMM Conv2D kernel
# ---------------------------------------------------------------------------

CONV_SHAPES = [
    # B, H, W, Cin, Cout, K, stride
    (1, 16, 16, 8, 8, 3, 1),     # aligned, square
    (2, 9, 13, 6, 10, 3, 1),     # odd H/W, non-multiple-of-block
    (1, 17, 11, 4, 4, 3, 2),     # stride-2 downsample, odd H/W
    (2, 12, 12, 8, 16, 1, 1),    # 1x1 skip conv
    (1, 8, 10, 6, 12, 1, 2),     # 1x1 stride-2
]

# tiny block sizes force multi-block grids (row halo + cin/cout reduction)
_CONV_BLOCKS = dict(block_rows=40, block_cin=4, block_cout=8)


def _conv_inputs(shape, dtype, kseed=0):
    B, H, W, Cin, Cout, K, s = shape
    key = jax.random.PRNGKey(kseed)
    sub = lambda i: jax.random.fold_in(key, i)
    x = jax.random.normal(sub(0), (B, H, W, Cin), dtype)
    w = (jax.random.normal(sub(1), (K, K, Cin, Cout)) * 0.2).astype(dtype)
    pad = K // 2
    OH, OW = (H + 2 * pad - K) // s + 1, (W + 2 * pad - K) // s + 1
    ep = dict(
        bias=jax.random.normal(sub(2), (Cout,)) * 0.1,
        temb=jax.random.normal(sub(3), (B, Cout)),
        residual=jax.random.normal(sub(4), (B, OH, OW, Cout), dtype),
        gn_affine=conv_ops.groupnorm_affine(
            x, jnp.ones(Cin) * 1.1, jnp.full(Cin, 0.05), groups=2),
    )
    return x, w, s, ep


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", CONV_SHAPES)
def test_conv2d_matches_oracle(shape, dtype):
    x, w, s, _ = _conv_inputs(shape, dtype)
    gold = conv_ref.conv2d_ref(x, w, stride=s)
    out = conv_ops.conv2d(x, w, stride=s, impl="interpret", **_CONV_BLOCKS)
    np.testing.assert_allclose(
        out.astype(jnp.float32), gold.astype(jnp.float32), **_tol(dtype)
    )


EPILOGUES = [
    dict(bias=True),
    dict(bias=True, temb=True),
    dict(bias=True, silu=True),
    dict(bias=True, residual=True),
    dict(gn=True),
    dict(gn=True, gn_silu=False),
    dict(gn=True, bias=True, temb=True, emit_stats=True),
    dict(gn=True, bias=True, silu=True, residual=True, emit_stats=True),
]


@pytest.mark.parametrize("combo", EPILOGUES)
@pytest.mark.parametrize("shape", [CONV_SHAPES[1], CONV_SHAPES[2]])
def test_conv2d_fused_epilogues(shape, combo):
    x, w, s, ep = _conv_inputs(shape, jnp.float32)
    kw = dict(
        stride=s,
        bias=ep["bias"] if combo.get("bias") else None,
        temb=ep["temb"] if combo.get("temb") else None,
        silu=combo.get("silu", False),
        residual=ep["residual"] if combo.get("residual") else None,
        gn_affine=ep["gn_affine"] if combo.get("gn") else None,
        gn_silu=combo.get("gn_silu", True),
        emit_stats=combo.get("emit_stats", False),
    )
    a, b = kw["gn_affine"] if kw["gn_affine"] is not None else (None, None)
    gold = conv_ref.conv2d_ref(
        x, w, stride=s, gn_a=a, gn_b=b, gn_silu=kw["gn_silu"], bias=kw["bias"],
        temb=kw["temb"], silu=kw["silu"], residual=kw["residual"],
        emit_stats=kw["emit_stats"],
    )
    for impl in ("interpret", "xla", "naive"):
        out = conv_ops.conv2d(x, w, impl=impl, **kw, **_CONV_BLOCKS)
        if kw["emit_stats"]:
            np.testing.assert_allclose(out[0], gold[0], rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(out[1], gold[1], rtol=2e-4, atol=2e-4)
        else:
            np.testing.assert_allclose(out, gold, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_channel_blocks_match_oracle(stride):
    """128-wide channel blocks: several C_in blocks reduce into one output
    block and several C_out blocks share an input block (with stride 2 the
    C_in blocks span both column phases)."""
    x, w, s, ep = _conv_inputs((2, 9, 12, 256, 256, 3, stride), jnp.float32)
    a, b = ep["gn_affine"]
    kw = dict(stride=s, bias=ep["bias"], temb=ep["temb"], emit_stats=True)
    gold = conv_ref.conv2d_ref(x, w, gn_a=a, gn_b=b, **kw)
    out = conv_ops.conv2d(x, w, gn_affine=(a, b), impl="interpret",
                          block_rows=24, block_cin=128, block_cout=128, **kw)
    np.testing.assert_allclose(out[0], gold[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[1], gold[1], rtol=1e-4, atol=1e-3)


def test_conv2d_grad_matches_xla():
    """The Pallas tiers define their backward pass through the xla ref."""
    x, w, s, ep = _conv_inputs(CONV_SHAPES[1], jnp.float32)

    def loss(impl):
        def f(x, w):
            y, st = conv_ops.conv2d(
                x, w, stride=s, bias=ep["bias"], gn_affine=ep["gn_affine"],
                temb=ep["temb"], residual=ep["residual"], emit_stats=True,
                impl=impl, **_CONV_BLOCKS)
            return (y ** 2).sum() + 1e-3 * (st ** 2).sum()
        return f

    g1 = jax.grad(loss("interpret"), argnums=(0, 1))(x, w)
    g2 = jax.grad(loss("xla"), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_conv2d_stats_match_groupnorm():
    """emit_stats -> affine_from_stats reproduces a direct GroupNorm affine."""
    x, w, s, ep = _conv_inputs(CONV_SHAPES[0], jnp.float32)
    Cout = w.shape[-1]
    scale = jnp.linspace(0.5, 1.5, Cout)
    bias = jnp.linspace(-0.2, 0.2, Cout)
    y, stats = conv_ops.conv2d(x, w, stride=s, bias=ep["bias"],
                               emit_stats=True, impl="interpret", **_CONV_BLOCKS)
    a1, b1 = conv_ops.affine_from_stats(
        stats, scale, bias, groups=2, count=y.shape[1] * y.shape[2])
    a2, b2 = conv_ops.groupnorm_affine(y, scale, bias, groups=2)
    np.testing.assert_allclose(a1, a2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("F,H,W,C", [(4, 8, 8, 8), (5, 7, 9, 6), (16, 4, 4, 12)])
def test_temporal_conv1d_fused_layout(F, H, W, C):
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (2, F, H, W, C))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, C, C)) * 0.2
    b = jax.random.normal(jax.random.fold_in(key, 2), (C,)) * 0.1
    gold = conv_ref.temporal_conv1d_ref(x, w, b)
    out = conv_ops.temporal_conv1d(x, w, b, impl="interpret", block_n=16)
    np.testing.assert_allclose(out, gold, rtol=2e-5, atol=2e-5)


GN_SHAPES = [(2, 1000, 256, 32, 256), (1, 64, 128, 8, 64), (3, 500, 96, 12, 128)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", GN_SHAPES)
@pytest.mark.parametrize("silu", [True, False])
def test_groupnorm_silu_matches_oracle(dtype, shape, silu):
    B, N, C, G, bn = shape
    key = jax.random.PRNGKey(1)
    x = (jax.random.normal(key, (B, N, C)) * 3 + 1).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), (C,)) * 0.5 + 1
    b = jax.random.normal(jax.random.fold_in(key, 2), (C,)) * 0.1
    gold = gn_ref.groupnorm_silu_ref(x, s, b, groups=G, silu=silu)
    out = gn_ops.groupnorm_silu(x, s, b, groups=G, silu=silu,
                                impl="interpret", block_n=bn)
    np.testing.assert_allclose(
        out.astype(jnp.float32), gold.astype(jnp.float32), **_tol(dtype)
    )
