"""Counter-based RNG facade.

Reference: libnd4j ``include/graph/RandomGenerator.h`` (Philox-style two-key
counter PRNG) and nd4j-api ``Nd4j.getRandom()``.

JAX's PRNG is already counter-based (threefry); this facade adds the stateful
ND4J surface (``setSeed``, draw methods) by splitting a root key per draw.
Inside jitted code use :meth:`split` / explicit keys instead.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.dtype import DataType, default_float


class RandomGenerator:
    """Stateful facade over a JAX PRNG key chain."""

    def __init__(self, seed: int = 119):
        self._lock = threading.Lock()
        self.setSeed(seed)

    def setSeed(self, seed: int) -> None:
        self._seed = int(seed)
        # made at the first draw: building a key initializes the backend,
        # and importing the package (the module-level default generator)
        # must not take the chip — it belongs to one process, and a
        # spawned worker that merely imports would fight its parent for it
        self._key = None

    def getSeed(self) -> int:
        return self._seed

    def split(self, n: int = 1):
        """Advance the counter and return ``n`` fresh subkeys (jit-safe input)."""
        with self._lock:
            if self._key is None:
                self._key = jax.random.PRNGKey(
                    self._seed & 0xFFFFFFFFFFFFFFFF)
            keys = jax.random.split(self._key, n + 1)
            self._key = keys[0]
        return keys[1] if n == 1 else keys[1:]

    # -- draw methods ---------------------------------------------------
    def uniform(self, shape, minval=0.0, maxval=1.0, dtype: DataType = None):
        dt = (dtype or default_float()).jnp
        return jax.random.uniform(self.split(), tuple(shape), dtype=dt,
                                  minval=minval, maxval=maxval)

    def normal(self, shape, mean=0.0, std=1.0, dtype: DataType = None):
        dt = (dtype or default_float()).jnp
        return jax.random.normal(self.split(), tuple(shape), dtype=dt) * std + mean

    def bernoulli(self, shape, p=0.5):
        return jax.random.bernoulli(self.split(), p, tuple(shape))

    def randint(self, shape, minval, maxval, dtype: DataType = DataType.INT32):
        return jax.random.randint(self.split(), tuple(shape), minval, maxval,
                                  dtype=dtype.jnp)

    def permutation(self, n: int):
        return jax.random.permutation(self.split(), int(n))

    def nextDouble(self) -> float:
        return float(jax.random.uniform(self.split(), ()))

    def nextGaussian(self) -> float:
        return float(jax.random.normal(self.split(), ()))

    def nextInt(self, bound: int) -> int:
        return int(jax.random.randint(self.split(), (), 0, int(bound)))


_DEFAULT = RandomGenerator(119)


def get_random() -> RandomGenerator:
    return _DEFAULT
