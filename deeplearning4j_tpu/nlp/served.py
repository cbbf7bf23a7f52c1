"""What the served LMs of this package share outside their blocks: the
prefill jitted once a prompt bucket under the bucket's name, the
full-sequence attention a block of queries at a time, the admission
write of a slot's own state, and rotary positions in the half-split
pairing.  ``OlmoHybridLM``, ``JambaLM``, ``SambaYLM``, ``PanguMoELM`` and
``KeyeVLLM`` call them; the mixers are the models' own
(:mod:`~deeplearning4j_tpu.nlp.mamba` for the two that run Mamba-1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["JitByLength", "attend_full", "slot_state_write"]

_F32 = jnp.float32
_I32 = jnp.int32
_NEG = -1e30
#: queries a block of the full-sequence attention holds against every key
QUERY_BLOCK = 512


def _rope(x, pos, theta: float):
    """``x (..., D)`` float32 turned by ``pos (...)``: lane ``i`` pairs
    with lane ``i + D / 2``, angle ``pos * theta^(-2 i / D)``."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(theta ** (-np.arange(half) / half), _F32)
    ang = pos[..., None].astype(_F32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


class JitByLength:
    """``run(params, tokens, start)`` jitted once for each length of
    ``tokens`` under that length's name (``jit_prefill_2048``), where the
    other served models keep one jit named ``jit_run`` for every bucket: a
    prefill of 512 positions and one of 4,096 differ by eight times in work,
    and a device trace then says which one it holds.  Stands where the one
    jit stood (called, counted by ``served_jit_entries``); ``at(t)`` is a
    length's own jit, for ``lower`` and ``trace``."""

    def __init__(self, run, name: str):
        self._run, self._name, self._jits = run, name, {}

    def at(self, t: int):
        if t not in self._jits:
            def run(*args):
                return self._run(*args)
            run.__name__ = f"{self._name}_{t}"
            self._jits[t] = jax.jit(run)
        return self._jits[t]

    def __call__(self, params, tokens, start):
        return self.at(tokens.shape[1])(params, tokens, start)

    def _cache_size(self) -> int:
        return sum(fn._cache_size() for fn in self._jits.values())


def attend_full(q, k, v, start, *, nHeads: int, nKvHeads: int):
    """Causal softmax attention over whole LEFT-padded sequences: ``q (b,
    T, H dh)`` and ``k, v (b, T, KV dh)`` as they are stored, in one
    dtype, query head ``a`` on KV head ``a // (H / KV)``; a block of
    :data:`QUERY_BLOCK` positions' queries at a time against every key,
    so that the scores of a whole bucket are never held at once.  No key
    before ``start (b,)`` is valid.  Returns ``(b, T, H dh)`` float32."""
    b, T, _ = q.shape
    G, r = nKvHeads, nHeads // nKvHeads
    dh = k.shape[-1] // G
    B = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    # the r query heads of a group ride as r query rows a position, so a
    # block is one matmul a KV head whatever the grouping
    q4 = q.reshape(b, T, G, r, dh).swapaxes(2, 3).reshape(b, T * r, G, dh)
    k4, v4 = k.reshape(b, T, G, dh), v.reshape(b, T, G, dh)
    kpos = jnp.arange(T, dtype=_I32)[None, None, :]
    real = kpos >= start[:, None, None]                      # (b, 1, T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q4, i * B * r, B * r, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k4,
                       preferred_element_type=_F32) * dh ** -0.5
        rows = i * B + jnp.arange(B * r, dtype=_I32) // r
        valid = (kpos <= rows[None, :, None]) & real         # (b, B r, T)
        a = jax.nn.softmax(jnp.where(valid[:, None], s, _NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a.astype(v4.dtype), v4,
                          preferred_element_type=_F32)
    o = jax.lax.map(block, jnp.arange(T // B, dtype=_I32))
    o = jnp.moveaxis(o, 0, 1).reshape(b, T, r, G, dh).swapaxes(2, 3)
    return o.reshape(b, T, nHeads * dh)


def slot_state_write(pool, part, slot):
    """One admitted sequence's share of a slot-state array: ``part
    (layers, ...)``, a prefill's batch row taken, over slot ``slot`` of
    ``pool (layers, slots, ...)``, which it overwrites whole (ring rows,
    recurrent state, convolution windows alike)."""
    z = jnp.zeros((), _I32)
    return jax.lax.dynamic_update_slice(
        pool, part[:, None].astype(pool.dtype),
        (z, slot.astype(_I32)) + (z,) * (pool.ndim - 2))
