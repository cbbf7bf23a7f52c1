"""Fused matmul+BN-stats Pallas epilogue (ops/pallas_fused.py).

Interpreter-mode parity on the CPU mesh; the module's docstring carries
the verdict an earlier round measured on the chip.
"""
import numpy as np
import jax.numpy as jnp

from deeplearning4j_tpu.ops.pallas_fused import (conv1x1_bn_stats,
                                                 matmul_bn_stats,
                                                 matmul_bn_stats_reference)


def test_matmul_bn_stats_parity():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1024, 192), jnp.float32)
    w = jnp.asarray(rng.randn(192, 256) * 0.05, jnp.float32)
    y, s, ss = matmul_bn_stats(x, w, block_m=256, block_n=128,
                               interpret=True)
    yr, sr, ssr = matmul_bn_stats_reference(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr), rtol=1e-5,
                               atol=1e-4)


def test_matmul_bn_stats_bf16_f32_accum():
    """bf16 inputs: y is bf16 but stats accumulate in f32 (parity with
    the f32 reference within bf16 matmul tolerance)."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(512, 128), jnp.bfloat16)
    w = jnp.asarray(rng.randn(128, 128) * 0.05, jnp.bfloat16)
    y, s, ss = matmul_bn_stats(x, w, block_m=256, interpret=True)
    assert y.dtype == jnp.bfloat16
    assert s.dtype == jnp.float32 and ss.dtype == jnp.float32
    _, sr, ssr = matmul_bn_stats_reference(x.astype(jnp.float32),
                                           w.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=2e-2, atol=2.0)


def test_bn_moments_from_stats():
    """mean/var derived from (sum, sumsq) match jnp.mean/var over rows —
    the BatchNorm consumption pattern."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(768, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 64) * 0.1, jnp.float32)
    y, s, ss = matmul_bn_stats(x, w, block_m=256, block_n=64,
                               interpret=True)
    m = 768.0
    mean = s / m
    var = ss / m - mean * mean
    np.testing.assert_allclose(np.asarray(mean),
                               np.asarray(jnp.mean(y, axis=0)), atol=1e-4)
    np.testing.assert_allclose(np.asarray(var),
                               np.asarray(jnp.var(y, axis=0)), atol=1e-4)


def test_conv1x1_wrapper():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 16, 16, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 64) * 0.1, jnp.float32)
    y, s, ss = conv1x1_bn_stats(x, w, block_m=256, block_n=64,
                                interpret=True)
    assert y.shape == (2, 16, 16, 64)
    ref = jnp.einsum("nhwc,cd->nhwd", x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(ref.reshape(-1, 64).sum(0)),
                               rtol=1e-5)
