"""What the served models of this package share outside their blocks.

:class:`ServedLM` is their base and owns the calling convention between
a model and ``ContinuousBatcher`` (``remote/scheduler.py``): the prefill
wrapper, the paged step and the admission write, built once from what a
model says of itself.  Beside it, what several models' blocks call: the
prefill jitted once a prompt bucket under the bucket's name, the
full-sequence attention a block of queries at a time, the admission
write of a slot's own state, and rotary positions in the half-split
pairing (the mixers are the models' own,
:mod:`~deeplearning4j_tpu.nlp.mamba` for the two that run Mamba-1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.attention import paged_rows_write

__all__ = ["ServedLM", "JitByLength", "attend_full", "paged_step_tokens",
           "slot_state_write"]

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
    jit stood (called, counted by ``ServedLM.compileCacheSize``); ``at(t)``
    is a length's own jit, for ``lower`` and ``trace``."""

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


def paged_step_tokens(toks, prev):
    """Where each slot's input token of a paged decode step comes from:
    ``toks`` ((S, tq) int32, from the host) wherever it names a token,
    and the step before's output ``prev`` ((S, 1), still on the device)
    wherever the host wrote ``-1`` because it had not read that token
    yet.  Part of the step's own program, so the scheduler's loop can
    dispatch a step before it has fetched the one before
    (``ContinuousBatcher``)."""
    return jnp.where(toks < 0, prev, toks)


class ServedLM:
    """A model behind ``ContinuousBatcher``: the one place that says how
    the scheduler calls a model.  A model supplies what is its own:

    - ``config.maxLen``, ``params`` and ``cacheSpec()``: what its layers
      keep between steps.  The pool's arrays, ``n`` of them in the order
      of ``cacheSpec().arrayKinds``, are what the step and the write
      below take and return;
    - ``_prefillRawFn``, a ``cached_property``: ``run(params, tokens (b,
      t), start (b,)) -> (last logits (b, vocab), *parts)`` jitted
      (``jax.jit(run)``, or ``JitByLength(run, "prefill")`` for a
      program a bucket), one part for each array of the pool in the
      pool's order: rows as :func:`paged_rows_write` takes them with a
      batch axis behind the layers', slot state ``(layers, b, ...)``;
    - ``pagedLogits(params, *arrays, toks (S, tq), pageTable, pos, start)
      -> (logits (S, tq, vocab), *arrays)``, pure;
    - ``stepCounters``, where its step counts on the device: ``(metric,
      labels[, unit])`` a column.  ``pagedLogits`` then returns one value
      more, ``counted (len(stepCounters),)`` int32, and the LAST array of
      the pool is the counts' carry: an admission ADDS its prefill's
      part to its slot's row, the step returns what it finds there in
      ``counted`` and hands the array back zeroed.

    From these the base builds, for every model alike, what the scheduler
    calls.  ``_fwd``, where a model has a full forward, is a
    ``cached_property`` too, and counted and dropped with the prefill.
    """

    #: the jits a model caches on itself (``cached_property``); its step
    #: and its write are built fresh for the scheduler, which owns them
    _SERVED_JITS = ("_fwd", "_prefillRawFn")
    stepCounters: tuple = ()

    def prefillRaw(self, tokens, lengths=None):
        """(b, t) LEFT-padded prompt, ``lengths`` its rows' real lengths
        (none padded where omitted) -> ``(last logits (b, vocab),
        *parts)`` as ``_prefillRawFn`` returns them.  Always mask-padded:
        one executable per prompt bucket whatever the raggedness."""
        tokens = jnp.asarray(tokens, _I32)
        t = tokens.shape[1]
        if t > self.config.maxLen:
            raise ValueError(f"prompt length {t} exceeds the capacity "
                             f"{self.config.maxLen}")
        if lengths is None:
            start = jnp.zeros((tokens.shape[0],), _I32)
        else:
            start = t - jnp.asarray(lengths, _I32)
        return self._prefillRawFn(self.params, tokens, start)

    def restartFromPrompt(self, tokens, lengths=None):
        """Restart hook for preemption and serving failover: rebuild a
        sequence's state from its ORIGINAL prompt, with exactly the
        dispatch the first admission used (same executable, same bucket
        shape), so the step-by-step replay that follows regenerates the
        identical token prefix.  The batcher additionally teacher-forces
        the tokens already delivered, so the prefix a client sees never
        depends on bit-wise reproducibility across replicas: a quantized
        or differently placed survivor can override this hook and still
        satisfy the exactly-once contract."""
        return self.prefillRaw(tokens, lengths=lengths)

    def buildPagedDecodeFn(self):
        """FRESH jitted decode step over a ``KVCachePool``'s arrays:
        ``step(params, *arrays, toks (S, 1), prev, pageTable, pos, start)
        -> (out, *arrays)``.  Column 0 of ``out (S, 1 +
        len(stepCounters))`` is the greedy token a slot; the columns
        behind it hold, in row 0, the counts ``stepCounters`` names.
        ``prev`` is the step before's ``out``, still on the device: a
        slot whose ``toks`` is -1 takes its first column
        (:func:`paged_step_tokens`).  The arrays are DONATED (the pool
        swaps in the returned ones).  A fresh function identity per
        build is deliberate: JAX's jaxpr cache keys on function identity
        + avals, so reusing one closure across a pool/plan rebuild could
        resurrect constraints traced for the old layout — the scheduler
        pops and rebuilds these on every pool/plan change.  Traces know
        the program by the inner function's name (``jit_step``)."""
        n = len(self.cacheSpec().arrayKinds)

        def step(params, *args):
            arrays, (toks, prev, pageTable, pos, start) = args[:n], args[n:]
            logits, *arrays = self.pagedLogits(
                params, *arrays, paged_step_tokens(toks, prev[:, :1]),
                pageTable, pos, start)
            out = jnp.argmax(logits, axis=-1).astype(_I32)    # (S, tq)
            if self.stepCounters:
                *arrays, counted = arrays
                tail = jnp.zeros((out.shape[0], counted.shape[0]), _I32
                                 ).at[0].set(counted)
                out = jnp.concatenate([out, tail], axis=1)
            return (out, *arrays)
        return jax.jit(step, donate_argnums=tuple(range(1, 1 + n)))

    def buildPagedPrefillWriteFn(self):
        """FRESH jitted admission write: ``write(*arrays, *parts,
        pageIds, slot) -> arrays``, one sequence's prefill state
        (:meth:`prefillRaw`'s parts, batch row taken) into the pool.  By
        the kind of each array (``cacheSpec().arrayKinds``): rows go into
        the pages ``pageIds`` ((Tp / pageSize,) int32, ``Tp`` a page
        multiple; :func:`paged_rows_write`), ring rows and recurrent
        state over slot ``slot``'s, whole (:func:`slot_state_write`), and
        the counts of a model with ``stepCounters`` are added to the
        slot's.  One cache entry per prompt bucket (warmed at start);
        the arrays are DONATED; traces know the program as
        ``jit_write``.  A model that keeps pages only may be called
        without the ``slot``."""
        kinds = self.cacheSpec().arrayKinds
        n = len(kinds)
        added = n - 1 if self.stepCounters else None

        def write(*args):
            arrays, parts, (pageIds, *slot) = args[:n], args[n:2 * n], \
                args[2 * n:]
            out = []
            for i, (kind, pool, part) in enumerate(zip(kinds, arrays, parts)):
                if kind in ("paged", "index"):
                    out.append(paged_rows_write(pool, part, pageIds))
                elif i == added:
                    out.append(pool.at[:, slot[0]].add(part))
                else:
                    out.append(slot_state_write(pool, part, slot[0]))
            return tuple(out)
        return jax.jit(write, donate_argnums=tuple(range(n)))

    def compileCacheSize(self) -> int:
        """Jit-cache entries of the forward and the prefill, the serving
        tier's compile hit/miss probe.  The batcher reads it every decode
        step, so it looks at built jits only and builds none."""
        n = 0
        for name in self._SERVED_JITS:
            fn = self.__dict__.get(name)
            if fn is not None:
                n += int(fn._cache_size())
        return n

    def dropCompiled(self) -> None:
        """Forget the cached jits (the scheduler calls this when the pool
        or the plan changes; the next call traces afresh): a reused
        closure would resurrect the old placement's trace."""
        for name in self._SERVED_JITS:
            self.__dict__.pop(name, None)
