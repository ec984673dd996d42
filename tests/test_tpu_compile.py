"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e.

Each case lowers one kernel at the published widths of the
stable-diffusion (and make-a-video temporal) path and compiles it with the
TPU compiler for a v5e chip that is described, not attached: nothing runs.
It catches what interpret mode cannot, such as a block that breaks the
(8, 128) tiling rule, a slice Mosaic refuses, or a tile that exhausts VMEM.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library.  Where it cannot
be described, every case skips.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.groupnorm_silu import ops as gn_ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep the cache out of the way
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _conv(**kw):
    def f(x, w, *ep):
        it = iter(ep)
        gn = (next(it), next(it)) if kw.get("gn") else None
        bias = next(it) if kw.get("bias") else None
        temb = next(it) if kw.get("temb") else None
        return conv_ops.conv2d(x, w, stride=kw.get("stride", 1), gn_affine=gn,
                               bias=bias, temb=temb,
                               emit_stats=kw.get("emit_stats", False),
                               impl="pallas")
    return f


def _conv_args(B, H, Cin, Cout, K=3, dtype=jnp.float32, **kw):
    args = [((B, H, H, Cin), dtype), ((K, K, Cin, Cout), dtype)]
    if kw.get("gn"):
        args += [((B, Cin), jnp.float32)] * 2
    if kw.get("bias"):
        args.append(((Cout,), dtype))
    if kw.get("temb"):
        args.append(((B, Cout), jnp.float32))
    return args


_RES = dict(gn=True, bias=True, temb=True, emit_stats=True)
_QKV = lambda B, S, Skv, H, D: [((B, S, H, D), jnp.float32)] + [
    ((B, Skv, H, D), jnp.float32)] * 2
_TQKV = [((1, 16, 1024, 5, 64), jnp.float32)] * 3  # 16 frames x 320 chans

CASES = {
    # UNet level-0 ResBlock conv: GN producer + temb + stats emission
    "conv_64x64x320_gn_temb_stats": (_conv(**_RES),
                                     _conv_args(2, 64, 320, 320, **_RES)),
    "conv_32x32x640_stride2": (_conv(stride=2), _conv_args(2, 32, 640, 640)),
    "conv_64x64x1280": (_conv(), _conv_args(1, 64, 1280, 1280)),
    "conv_in_4_to_320": (_conv(bias=True), _conv_args(2, 64, 4, 320, bias=True)),
    "vae_conv_512x512x128": (_conv(bias=True),
                             _conv_args(1, 512, 128, 128, bias=True)),
    "groupnorm_silu_4096x320": (
        lambda x, s, b: gn_ops.groupnorm_silu(x, s, b, groups=32,
                                              impl="pallas"),
        [((2, 4096, 320), jnp.float32), ((320,), jnp.float32),
         ((320,), jnp.float32)]),
    "flash_self_4096_d40": (lambda q, k, v: fa_ops.attention(q, k, v,
                                                             impl="pallas"),
                            _QKV(2, 4096, 4096, 8, 40)),
    "flash_cross_77": (lambda q, k, v: fa_ops.attention(q, k, v, impl="pallas"),
                       _QKV(2, 4096, 77, 8, 40)),
    "temporal_attention_16f_320c": (
        lambda q, k, v: fa_ops.temporal_attention(q, k, v, impl="pallas"),
        _TQKV),
    "temporal_conv1d_16f_320c": (
        lambda x, w, b: conv_ops.temporal_conv1d(x, w, b, impl="pallas"),
        [((1, 16, 32, 32, 320), jnp.float32), ((3, 320, 320), jnp.float32),
         ((320,), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
