"""Pallas TPU kernels for perf-critical compute hot-spots.

Each kernel lives in its own subpackage with:
  * ``<name>.py``   — the ``pl.pallas_call`` kernel with explicit BlockSpec VMEM tiling
  * ``ops.py``      — jit-friendly dispatching wrapper (pallas / interpret / pure-jnp paths)
  * ``ref.py``      — pure-jnp oracle used by tests and as the autodiff path

Kernels present:
  * ``flash_attention`` — FlashAttention-2-style online-softmax attention
    (causal / full / cross / GQA / local-window), plus a *temporal* variant
    that fuses the (B, F, HW, D) layout permutation of TTV temporal attention
    into the BlockSpec index_map (the TPU-native adaptation of the paper §VI).
  * ``groupnorm_silu`` — fused GroupNorm + SiLU for diffusion ResNet blocks
    (the paper's C1: GroupNorm is 4-11% of diffusion time).
  * ``conv2d`` — fused implicit-GEMM NHWC Conv2D (3x3 stride-1/2 and 1x1)
    with fused GroupNorm(+SiLU) producer, bias / time-embedding / SiLU /
    residual epilogues and next-GroupNorm stats emission, plus a fused-layout
    temporal Conv1D for TTV — targeting C1's post-FA bottleneck (Convolution
    is up to 44% of diffusion execution time).

The paper itself optimizes exactly one hot-spot (Attention, via Flash
Attention); the flash kernel is therefore the paper-faithful artifact, and
groupnorm_silu / conv2d are beyond-paper additions targeting the post-FA
bottleneck the paper identifies.
"""

# Scoped VMEM limit the kernels are compiled with.  One v5e TensorCore has
# 128 MiB of VMEM; Mosaic's default scope is 16 MiB, too small for
# full-width diffusion tiles.  The margin is Mosaic's own scratch.
VMEM_LIMIT = 100 * 2**20
