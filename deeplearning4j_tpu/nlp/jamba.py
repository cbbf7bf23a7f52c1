"""Jamba LM (AI21-Jamba2-3B; the hybrid of arXiv:2403.19887) for the
serving tier: Mamba-1 layers with an RMSNorm on each of ``Δ``'s inputs,
``B`` and ``C``, and one grouped-query attention layer to every
``attnPeriod`` layers (layer ``i`` is attention where ``i % attnPeriod ==
attnOffset``: 2 of 28 as published, 20 query heads on ONE KV head).
Every layer is pre-RMSNorm mixer + pre-RMSNorm gated-SiLU FFN (one dense
expert: no router); no positional encoding anywhere (the convolutions and
the decay carry order); a final RMSNorm and a tied output head.

The Mamba mixer is :mod:`deeplearning4j_tpu.nlp.mamba`'s, the one
``SambaYLM`` calls.  What the model keeps between decode steps is TWO
kinds of state, named by :meth:`JambaLM.cacheSpec` and held side by side
by the scheduler's ``KVCachePool``:

- *paged* — the attention layers' K/V rows, one per position, in pages
  that grow with the sequence; read through
  :func:`~deeplearning4j_tpu.nn.conf.attention.paged_attention`, so on
  one TPU by the kernel that reads the live pages where they lie, every
  query head of a group on its KV head's lanes;
- *recurrent* — each Mamba layer's float32 state ``(N, d_in)`` and the
  convolution's last ``K - 1`` inputs per slot, overwritten every step.

Precision: weights, residual stream and K/V in the parameters' dtype
(bfloat16 as served); the SSM state, ``Δ``/``exp``, softmax, norms and
logits in float32; every matmul accumulates in float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.attention import (CacheSpec,
                                                  paged_attention)
from deeplearning4j_tpu.nlp.mamba import _mm, _rms, mamba_full, mamba_step
from deeplearning4j_tpu.nlp.served import (JitByLength, ServedLM,
                                           attend_full)

__all__ = ["JambaConfig", "JambaLM"]

_F32 = jnp.float32
_I32 = jnp.int32


@dataclasses.dataclass
class JambaConfig:
    vocabSize: int = 256
    nLayers: int = 8
    hiddenSize: int = 64
    nHeads: int = 4
    nKvHeads: int = 1
    ffnSize: int = 128
    attnPeriod: int = 4         # layer i is attention where
    attnOffset: int = 2         # i % attnPeriod == attnOffset
    stateSize: int = 4          # N
    convKernel: int = 4         # K
    expand: int = 2             # d_in = expand * hiddenSize
    dtRank: int = 4             # R
    eps: float = 1e-6
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def headSize(self) -> int:
        return self.hiddenSize // self.nHeads

    @property
    def innerSize(self) -> int:
        return self.expand * self.hiddenSize

    def layerKinds(self) -> List[str]:
        return ["attention" if i % self.attnPeriod == self.attnOffset
                else "mamba" for i in range(self.nLayers)]


class JambaLM(ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns both kinds of cache state, and
    the step form ``pagedLogits``, from which ``ServedLM`` builds the
    scheduler's fixed-shape decode step and admission write."""

    def __init__(self, config: Optional[JambaConfig] = None, params=None,
                 **kw):
        self.config = config or JambaConfig(**kw)
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype, one
        small program per kind of layer."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, ff, dIn = c.hiddenSize, c.ffnSize, c.innerSize
        N, K, R, dh = c.stateSize, c.convKernel, c.dtRank, c.headSize
        std = c.initializerRange

        @functools.partial(jax.jit, static_argnames=("kind",))
        def layer(key, kind):
            keys = iter(jax.random.split(key, 16))
            normal = lambda shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            uniform = lambda shape, b: jax.random.uniform(
                next(keys), shape, _F32, -b, b).astype(dt)
            ones = lambda n: jnp.ones((n,), dt)
            p = {"norm1": ones(d), "norm2": ones(d),
                 "Wgate": normal((d, ff)), "Wup": normal((d, ff)),
                 "Wdown": normal((ff, d))}
            if kind == "mamba":
                dtv = jnp.exp(jax.random.uniform(next(keys), (dIn,), _F32)
                              * (math.log(1e-1) - math.log(1e-3))
                              + math.log(1e-3))
                p.update(
                    Win=normal((d, 2 * dIn)),
                    convW=uniform((K, dIn), K ** -0.5),
                    convB=uniform((dIn,), K ** -0.5),
                    Wx=normal((dIn, R + 2 * N)),
                    Wdt=uniform((R, dIn), R ** -0.5),
                    bdt=(dtv + jnp.log(-jnp.expm1(-dtv))).astype(dt),
                    AlogT=jnp.broadcast_to(jnp.log(jnp.arange(
                        1, N + 1, dtype=_F32))[:, None], (N, dIn)).astype(dt),
                    D=ones(dIn), Wout=normal((dIn, d)),
                    dtNorm=ones(R), bNorm=ones(N), cNorm=ones(N))
            else:
                p.update(Wq=normal((d, c.nHeads * dh)),
                         Wk=normal((d, c.nKvHeads * dh)),
                         Wv=normal((d, c.nKvHeads * dh)),
                         Wo=normal((c.nHeads * dh, d)))
            return p

        @jax.jit
        def embedding(key):
            return (std * jax.random.normal(key, (c.vocabSize, d), _F32)
                    ).astype(dt)

        key = jax.random.PRNGKey(c.seed)
        return {"emb": embedding(jax.random.fold_in(key, 0)),
                "normf": jnp.ones((d,), dt),
                "layers": [layer(jax.random.fold_in(key, i + 1), kind)
                           for i, kind in enumerate(c.layerKinds())]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        pages for the attention layers (a row is the KV heads side by
        side: ONE head of 128 lanes as published), no ring, and the Mamba
        layers' state and convolution windows."""
        c = self.config
        kinds = c.layerKinds()
        nM = kinds.count("mamba")
        dt = jnp.dtype(c.dtype)
        return CacheSpec(
            pagedLayers=kinds.count("attention"), kvHeads=c.nKvHeads,
            headSize=c.headSize, dtype=dt,
            slotState=(("ssm", (nM, c.stateSize, c.innerSize), _F32),
                       ("conv", (nM, c.convKernel - 1, c.innerSize), dt)))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _ffn(self, lp, x):
        u = _rms(x, lp["norm2"], self.config.eps)
        g = jax.nn.silu(_mm(u, lp["Wgate"])) * _mm(u, lp["Wup"])
        return x + _mm(g, lp["Wdown"]).astype(x.dtype)

    def _logits(self, params, x):
        h = _rms(x, params["normf"], self.config.eps)
        emb = params["emb"]
        return jax.lax.dot_general(
            h.astype(emb.dtype), emb,
            (((h.ndim - 1,), (1,)), ((), ())), preferred_element_type=_F32)

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill
    # ------------------------------------------------------------------
    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last layer's output and the cache state a
        decode would continue from: the attention layers' K/V rows, every
        Mamba layer's final state and last ``K - 1`` convolution inputs.
        A pad position advances nothing (:func:`mamba_full`), and no key
        is valid there."""
        c = self.config
        T = tokens.shape[1]
        realF = (jnp.arange(T, dtype=_I32)[None, :] >= start[:, None]
                 ).astype(_F32)[..., None]                   # (b, T, 1)
        x = params["emb"][tokens]
        cd = x.dtype
        pagedK, pagedV, ssm, conv = [], [], [], []
        for kind, lp in zip(c.layerKinds(), params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            if kind == "mamba":
                out, _, s, tail = mamba_full(
                    lp, h, realF, N=c.stateSize, K=c.convKernel, R=c.dtRank,
                    eps=c.eps)
                ssm.append(s)
                conv.append(tail.astype(cd))
            else:
                q = _mm(h, lp["Wq"]).astype(cd)
                kR = _mm(h, lp["Wk"]).astype(cd)
                vR = _mm(h, lp["Wv"]).astype(cd)
                pagedK.append(kR)
                pagedV.append(vR)
                out = _mm(attend_full(q, kR, vR, start, nHeads=c.nHeads,
                                      nKvHeads=c.nKvHeads), lp["Wo"])
            x = self._ffn(lp, x + out.astype(cd))
        # the paged stacks in paged_rows_write's form (L, b, h, T, d):
        # one "head" as wide as a row
        return x, (jnp.stack(pagedK)[:, :, None], jnp.stack(pagedV)[:, :, None],
                   jnp.stack(ssm), jnp.stack(conv))

    @functools.cached_property
    def _fwd(self):
        def run(params, tokens):
            start = jnp.zeros((tokens.shape[0],), _I32)
            x, _ = self._run_full(params, tokens, start)
            return self._logits(params, x)
        return jax.jit(run)

    def forward(self, tokens) -> jax.Array:
        """Full causal forward: (b, t) int32 -> (b, t, vocab) float32."""
        return self._fwd(self.params, jnp.asarray(tokens, _I32))

    @functools.cached_property
    def _prefillRawFn(self):
        """``(last logits (b, vocab), kStack, vStack, ssm, conv)``: the
        paged stacks in :func:`paged_rows_write`'s form ``(attention
        layers, b, 1, t, KV*dh)`` and the slot state ``(Mamba layers, b,
        ...)`` in the pool's order."""
        def run(params, tokens, start):
            x, state = self._run_full(params, tokens, start)
            return (self._logits(params, x[:, -1]),) + state
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, k, v, ssm, conv, toks, pageTable, pos,
                    start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays: ``((S, 1, vocab) logits, k, v, ssm, conv)``.  A slot
        whose ``pos`` is 0 holds no sequence (or is deferred a round):
        its paged write lands on the scratch page through its zeroed page
        table, and its recurrent state is left as it is."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "a recurrent state advances one token a step: speculative "
                "verification (tq > 1) would need its roll-back")
        dh = c.headSize
        active = pos > 0
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        keep = lambda new, old: jnp.where(
            active.reshape((S,) + (1,) * (new.ndim - 1)), new, old)
        heads = lambda a: a.reshape(S, 1, -1, dh).transpose(0, 2, 1, 3)
        mi = ai = 0
        for kind, lp in zip(c.layerKinds(), params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            if kind == "mamba":
                out, _, s, win = mamba_step(
                    lp, h, ssm[mi], conv[mi], keep, N=c.stateSize,
                    R=c.dtRank, eps=c.eps)
                conv = conv.at[mi].set(win)
                ssm = ssm.at[mi].set(s)
                mi += 1
            else:
                ctx, k, v = paged_attention(
                    heads(_mm(h, lp["Wq"])), heads(_mm(h, lp["Wk"])),
                    heads(_mm(h, lp["Wv"])), k, v, ai, pageTable, pos, start)
                out = _mm(ctx.transpose(0, 2, 1, 3).reshape(S, -1), lp["Wo"])
                ai += 1
            x = self._ffn(lp, x + out.astype(cd))
        return self._logits(params, x)[:, None], k, v, ssm, conv
