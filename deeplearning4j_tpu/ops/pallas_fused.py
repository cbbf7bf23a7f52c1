"""Pallas fused-epilogue kernels: matmul with batch-norm statistics.

Motivation: an earlier round profiled the ResNet-50 train step as
HBM-bound, with BatchNorm's statistics passes ~21 GB/step of the traffic —
XLA computes ``y = conv(x, w)`` (one full write of y), then reduces y
again for the per-channel mean/variance (one full re-READ of y).  On TPU
the conv/matmul is a fusion *boundary*, so XLA cannot sink the reduction
into the conv's output loop.  A Pallas kernel can: each output tile's
column-sums are accumulated into VMEM-resident stats blocks while the
tile is still on-chip, eliminating the re-read entirely.

``matmul_bn_stats(x, w)`` returns ``(y, sum, sumsq)`` per output column
(= per conv channel when the conv is expressed as an im2col/1x1 GEMM,
NHWC-flattened: x (N*H*W, Cin), w (Cin, Cout)).  BatchNorm mean/var then
derive as ``mean = s/M``, ``var = ss/M - mean^2`` without touching y.

Reference: this replaces the stats half of
``org/deeplearning4j/nn/layers/normalization/BatchNormalization`` 's
forward helper (cudnnBatchNormalizationForwardTraining fuses the same
way on GPU — SURVEY §2.5); the TPU-native answer is a Pallas epilogue
rather than a cuDNN call.

Verdict measured on a v5e in an earlier round, on older code (today's
code: not measured): **negative** — XLA's matmul kernels beat this
hand-tiled Pallas GEMM by 0.5–4 ms at ResNet conv-as-GEMM shapes, an
order of magnitude more than the one-read-of-y the epilogue saves
(0.03–0.5 ms).  The kernel stays in-tree as the
measured prototype and as the template for epilogue fusions where XLA
has no fused primitive at all (cf. the flash-attention kernel in
parallel/ring.py, which does win).  Do NOT wire this into the conv+BN
path expecting a speedup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_bn_stats", "matmul_bn_stats_reference"]


def matmul_bn_stats_reference(x, w):
    """Unfused XLA reference: matmul, then a second pass over y for the
    stats (what XLA emits for conv→BN today: the reduce re-reads y)."""
    y = jnp.matmul(x, w)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, axis=0), jnp.sum(yf * yf, axis=0)


def _mm_bn_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref):
    # grid = (n_tiles_N, n_tiles_M): j (cols) outer, i (rows) inner, so
    # the stats block for column-tile j stays VMEM-resident across the
    # whole i sweep and is written back to HBM exactly once per j.
    i = pl.program_id(1)
    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    s_ref[:] = s_ref[:] + jnp.sum(y, axis=0, keepdims=True)
    ss_ref[:] = ss_ref[:] + jnp.sum(y * y, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def matmul_bn_stats(x, w, block_m: int = 512, block_n: int = 128,
                    interpret: bool = False):
    """``y = x @ w`` plus per-column ``(sum, sum-of-squares)`` of y,
    computed in the matmul's epilogue (y is never re-read from HBM).

    x: (M, K), w: (K, N); M % block_m == 0, N % block_n == 0 (pad the
    GEMM, not the kernel — ResNet im2col shapes are 128-multiples).
    Returns (y (M,N) x.dtype, sum (N,) f32, sumsq (N,) f32).
    Stats accumulate in f32 regardless of input dtype.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m, block_n = min(block_m, m), min(block_n, n)
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)

    grid = (n // block_n, m // block_m)
    y, s, ss = pl.pallas_call(
        _mm_bn_kernel,
        grid=grid,
        in_specs=[
            # x tile re-streams once per column tile; w tile once per row
            # sweep.  ``i * 0``/``j * 0`` keep index maps i32 under the
            # package's jax_enable_x64 (see ring.py note).
            pl.BlockSpec((block_m, k), lambda j, i: (i, j * 0)),
            pl.BlockSpec((k, block_n), lambda j, i: (i * 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda j, i: (i, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (i * 0, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (i * 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
    return y, s[0], ss[0]


def conv1x1_bn_stats(x_nhwc, w, block_m: int = 512, block_n: int = 128,
                     interpret: bool = False):
    """1x1 conv (stride 1) + BN stats via the fused GEMM: x (N,H,W,Cin),
    w (Cin, Cout) -> (y (N,H,W,Cout), sum (Cout,), sumsq (Cout,))."""
    n, h, w_, cin = x_nhwc.shape
    cout = w.shape[1]
    y, s, ss = matmul_bn_stats(x_nhwc.reshape(n * h * w_, cin), w,
                               block_m=block_m, block_n=block_n,
                               interpret=interpret)
    return y.reshape(n, h, w_, cout), s, ss
