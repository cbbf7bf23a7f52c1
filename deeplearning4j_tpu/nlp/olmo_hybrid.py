"""Olmo-Hybrid LM for the serving tier: three Gated-DeltaNet
linear-attention layers (arXiv:2412.06464) to every full-attention layer,
in the Olmo 2/3 block with its reordered norm (arXiv:2501.00656):
``y = x + RMSNorm(mixer(x))``, ``out = y + RMSNorm(FFN(y))``, q/k
normalised over the whole width, no positional encoding (the
convolutions and the decay carry order), a final RMSNorm and an untied
head.  Layer ``i`` is full attention where ``i % 4 == 3``.

What the model keeps between decode steps is TWO kinds of state, named by
:meth:`OlmoHybridLM.cacheSpec` and held side by side by the scheduler's
``KVCachePool``:

- *paged* — every full layer's K (after its norm) and V rows, one per
  position, in pages that grow with the sequence; read through
  :func:`~deeplearning4j_tpu.nn.conf.attention.paged_attention`, so on
  one TPU by the kernel that reads the live pages where they lie;
- *recurrent* — every linear layer's float32 state, a ``(dk, dv)``
  MATRIX a head updated by a rank-one delta rule, and the last ``K - 1``
  inputs of its three depthwise convolutions; overwritten every step, in
  place.

The delta rule, with ONE decay a head, is :mod:`~deeplearning4j_tpu.nlp
.delta`'s, which ``LingLM`` calls too with a decay a channel.  The decode
step runs the recurrence itself, one token a slot
(:func:`~deeplearning4j_tpu.nlp.delta.delta_rule_step`): ``S' = a S; u =
b (v - S'^T k); S = S' + k u^T; o = S^T q``.  Forward and prefill run its
CHUNKED form (:func:`~deeplearning4j_tpu.nlp.delta.delta_rule_chunked`),
so a prompt of 4,096 positions is 64 steps of a scan and not 4,096.

Precision: weights, residual stream and K/V in the parameters' dtype
(bfloat16 as served); the delta state, ``alpha``/``beta``, the q/k
normalisations, the chunk's transform, softmax, norms and logits in
float32; every matmul accumulates in float32.
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
from deeplearning4j_tpu.nlp.delta import (delta_rule_chunked,
                                          delta_rule_step, l2_normalise,
                                          short_conv_full, short_conv_step)
from deeplearning4j_tpu.nlp.mamba import _mm, _rms
from deeplearning4j_tpu.nlp.served import (JitByLength, ServedLM,
                                           attend_full)

__all__ = ["OlmoHybridConfig", "OlmoHybridLM"]

_F32 = jnp.float32
_I32 = jnp.int32


@dataclasses.dataclass
class OlmoHybridConfig:
    vocabSize: int = 256
    nLayers: int = 8
    hiddenSize: int = 64
    nHeads: int = 4             # full-attention heads of hiddenSize / nHeads
    ffnSize: int = 128
    linHeads: int = 4           # key heads = value heads of the linear layers
    linKeyDim: int = 8          # dk
    linValueDim: int = 16       # dv
    convKernel: int = 4         # K
    fullEvery: int = 4          # layer i is full where i % fullEvery is last
    chunk: int = 64             # C of the chunked delta rule, a power of two
    eps: float = 1e-6
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def headSize(self) -> int:
        return self.hiddenSize // self.nHeads

    @property
    def convWidth(self) -> int:
        """Channels of the three convolutions side by side: q, k, v."""
        return self.linHeads * (2 * self.linKeyDim + self.linValueDim)

    def layerKinds(self) -> List[str]:
        return ["full" if i % self.fullEvery == self.fullEvery - 1
                else "linear" for i in range(self.nLayers)]


class OlmoHybridLM(ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns both kinds of cache state, and
    the step form ``pagedLogits``, from which ``ServedLM`` builds the
    scheduler's fixed-shape decode step and admission write."""

    def __init__(self, config: Optional[OlmoHybridConfig] = None,
                 params=None, **kw):
        self.config = config or OlmoHybridConfig(**kw)
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype, one
        small program per kind of layer."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, ff, H, dk, dv = (c.hiddenSize, c.ffnSize, c.linHeads,
                            c.linKeyDim, c.linValueDim)
        K, std = c.convKernel, c.initializerRange

        @functools.partial(jax.jit, static_argnames=("kind",))
        def layer(key, kind):
            keys = iter(jax.random.split(key, 20))
            normal = lambda shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            conv = lambda width: jax.random.uniform(
                next(keys), (K, width), _F32, -K ** -0.5, K ** -0.5
            ).astype(dt)
            ones = lambda n: jnp.ones((n,), dt)
            p = {"norm1": ones(d), "norm2": ones(d),
                 "Wgate": normal((d, ff)), "Wup": normal((d, ff)),
                 "Wdown": normal((ff, d))}
            if kind == "linear":
                A = jax.random.uniform(next(keys), (H,), _F32, 1e-4, 16.0)
                dtv = jnp.exp(jax.random.uniform(next(keys), (H,), _F32)
                              * (math.log(1e-1) - math.log(1e-3))
                              + math.log(1e-3))
                p.update(
                    Wq=normal((d, H * dk)), Wk=normal((d, H * dk)),
                    Wv=normal((d, H * dv)), Wg=normal((d, H * dv)),
                    Wo=normal((H * dv, d)), Wa=normal((d, H)),
                    Wb=normal((d, H)), convQ=conv(H * dk),
                    convK=conv(H * dk), convV=conv(H * dv),
                    Alog=jnp.log(A).astype(dt),
                    dtBias=(dtv + jnp.log(-jnp.expm1(-dtv))).astype(dt),
                    gnorm=ones(dv))
            else:
                p.update(Wq=normal((d, d)), Wk=normal((d, d)),
                         Wv=normal((d, d)), Wo=normal((d, d)),
                         qnorm=ones(d), knorm=ones(d))
            return p

        @jax.jit
        def ends(key):
            ke, kh = jax.random.split(key)
            return ((std * jax.random.normal(ke, (c.vocabSize, d), _F32)
                     ).astype(dt),
                    (std * jax.random.normal(kh, (d, c.vocabSize), _F32)
                     ).astype(dt))

        key = jax.random.PRNGKey(c.seed)
        emb, head = ends(jax.random.fold_in(key, 0))
        return {"emb": emb, "head": head, "normf": jnp.ones((d,), dt),
                "layers": [layer(jax.random.fold_in(key, i + 1), kind)
                           for i, kind in enumerate(c.layerKinds())]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        pages for the full layers (rows of all heads side by side), no
        ring, and the linear layers' delta state and convolution
        windows."""
        c = self.config
        kinds = c.layerKinds()
        nL = kinds.count("linear")
        dt = jnp.dtype(c.dtype)
        return CacheSpec(
            pagedLayers=kinds.count("full"), kvHeads=c.nHeads,
            headSize=c.headSize, dtype=dt,
            slotState=(("delta", (nL, c.linHeads, c.linKeyDim,
                                  c.linValueDim), _F32),
                       ("conv", (nL, c.convKernel - 1, c.convWidth), dt)))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _gates(self, lp, h):
        """``beta`` and ``g = log alpha`` ``(..., H)`` from ``h (..., d)``,
        float32."""
        f = lambda n: lp[n].astype(_F32)
        beta = 2.0 * jax.nn.sigmoid(_mm(h, lp["Wb"]))
        g = -jnp.exp(f("Alog")) * jax.nn.softplus(_mm(h, lp["Wa"])
                                                  + f("dtBias"))
        return beta, g

    def _heads(self, q, k, v):
        """The three convolutions' outputs ``(..., H dk)``, ``(..., H
        dk)``, ``(..., H dv)`` through their SiLU and into heads: ``q``
        and ``k`` normalised ``(..., H, dk)``, ``v (..., H, dv)``."""
        c = self.config
        H, dk = c.linHeads, c.linKeyDim
        split = lambda a: jax.nn.silu(a).reshape(a.shape[:-1] + (H, -1))
        return (l2_normalise(split(q)) * dk ** -0.5,
                l2_normalise(split(k)), split(v))

    def _gdn_out(self, lp, o, h):
        """``(RMSNorm_dv(o) * g_norm * silu(h Wg)) Wo``."""
        o = _rms(o, lp["gnorm"], self.config.eps)
        o = o.reshape(o.shape[:-2] + (-1,))
        return _mm(o * jax.nn.silu(_mm(h, lp["Wg"])), lp["Wo"])

    def _close_block(self, lp, x, out, hold=lambda a: a):
        """The block around a mixer's output: both residual adds, each
        behind its norm, and the gated FFN.  ``hold`` is applied to the
        stream after each add."""
        eps = self.config.eps
        y = hold(x + _rms(out, lp["norm1"], eps).astype(x.dtype))
        ff = _mm(jax.nn.silu(_mm(y, lp["Wgate"])) * _mm(y, lp["Wup"]),
                 lp["Wdown"])
        return hold(y + _rms(ff, lp["norm2"], eps).astype(x.dtype))

    def _logits(self, params, x):
        return _mm(_rms(x, params["normf"], self.config.eps), params["head"])

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill
    # ------------------------------------------------------------------
    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last layer's output and the cache state a
        decode would continue from: every full layer's K/V rows, every
        linear layer's final delta state and last ``K - 1`` convolution
        inputs.  A pad position changes nothing: its convolution inputs
        are zero (so its q, k, v are), its ``beta`` is 0 and its
        ``alpha`` 1, and no key is valid there."""
        c = self.config
        b, T = tokens.shape
        realF = (jnp.arange(T, dtype=_I32)[None, :] >= start[:, None]
                 ).astype(_F32)[..., None]                   # (b, T, 1)
        x = params["emb"][tokens]
        cd = x.dtype
        kinds = c.layerKinds()
        spec = self.cacheSpec()
        # each layer's state goes into its row of the stacks as soon as it
        # exists (a list stacked at the end would hold all of it twice:
        # 0.57 GB at the published sizes and 4,096 positions); the paged
        # stacks in paged_rows_write's form (L, b, h, T, d), one
        # "head" as wide as a row
        pagedK = pagedV = jnp.zeros(
            (spec.pagedLayers, b, 1, T, spec.rowWidth), cd)
        (_, dShape, _), (_, cShape, _) = spec.slotState
        delta = jnp.zeros(dShape[:1] + (b,) + dShape[1:], _F32)
        conv = jnp.zeros(cShape[:1] + (b,) + cShape[1:], cd)
        li = fi = 0
        for kind, lp in zip(kinds, params["layers"]):
            if kind == "linear":
                # q, k and v one after the other (side by side they are
                # 189 MB in float32 at 4,096 positions, three times over)
                convolved = lambda w, taps: short_conv_full(
                    _mm(x, lp[w]) * realF, lp[taps])
                (q, tq), (k, tk), (v, tv) = (
                    convolved("Wq", "convQ"), convolved("Wk", "convK"),
                    convolved("Wv", "convV"))
                conv = conv.at[li].set(
                    jnp.concatenate([tq, tk, tv], axis=-1).astype(cd))
                q, k, v = self._heads(q, k, v)
                beta, g = self._gates(lp, x)
                o, S = delta_rule_chunked(q, k, v, beta * realF, g * realF,
                                          c.chunk)
                delta = delta.at[li].set(S)
                out = self._gdn_out(lp, o, x)
                li += 1
            else:
                q = _rms(_mm(x, lp["Wq"]), lp["qnorm"], c.eps)
                kR = _rms(_mm(x, lp["Wk"]), lp["knorm"], c.eps).astype(cd)
                vR = _mm(x, lp["Wv"]).astype(cd)
                pagedK = pagedK.at[fi, :, 0].set(kR)
                pagedV = pagedV.at[fi, :, 0].set(vR)
                out = _mm(attend_full(q.astype(cd), kR, vR, start,
                                      nHeads=c.nHeads, nKvHeads=c.nHeads),
                          lp["Wo"])
                fi += 1
            # the stream is written out after every block: left to itself
            # XLA keeps each block's float32 contribution instead and has
            # every later consumer add them all up again (32 buffers of
            # 63 MB live to the end of a 4,096-position prefill)
            x = self._close_block(lp, x, out,
                                  hold=jax.lax.optimization_barrier)
        return x, (pagedK, pagedV, delta, conv)

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
        """``(last logits (b, vocab), kStack, vStack, delta, conv)``: the
        paged stacks in :func:`paged_rows_write`'s form ``(full layers, b,
        1, t, d)`` and the slot state ``(linear layers, b, ...)`` in the
        pool's order."""
        def run(params, tokens, start):
            x, state = self._run_full(params, tokens, start)
            return (self._logits(params, x[:, -1]),) + state
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, k, v, delta, conv, toks, pageTable, pos,
                    start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays: ``((S, 1, vocab) logits, k, v, delta, conv)``.  A slot
        whose ``pos`` is 0 holds no sequence (or is deferred a round):
        its paged write lands on the scratch page through its zeroed page
        table, and its recurrent state is left as it is."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "a recurrent state advances one token a step: speculative "
                "verification (tq > 1) would need its roll-back")
        H, dh = c.nHeads, c.headSize
        active = pos > 0
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        keep = lambda new, old: jnp.where(
            active.reshape((S,) + (1,) * (new.ndim - 1)), new, old)
        heads = lambda a: a.reshape(S, 1, H, dh).transpose(0, 2, 1, 3)
        li = fi = 0
        for kind, lp in zip(c.layerKinds(), params["layers"]):
            if kind == "linear":
                qkv = jnp.concatenate([_mm(x, lp["Wq"]), _mm(x, lp["Wk"]),
                                       _mm(x, lp["Wv"])], axis=-1)
                taps = jnp.concatenate(
                    [lp["convQ"], lp["convK"], lp["convV"]], axis=-1)
                u, win = short_conv_step(conv[li], qkv, taps)
                conv = conv.at[li].set(keep(win.astype(conv.dtype),
                                            conv[li]))
                nk = c.linHeads * c.linKeyDim
                q, kk, vv = self._heads(u[:, :nk], u[:, nk:2 * nk],
                                        u[:, 2 * nk:])
                beta, g = self._gates(lp, x)
                Sd, o = delta_rule_step(delta[li], q, kk, vv, beta,
                                        jnp.exp(g)[..., None, None])
                delta = delta.at[li].set(keep(Sd, delta[li]))
                out = self._gdn_out(lp, o, x)
                li += 1
            else:
                q = _rms(_mm(x, lp["Wq"]), lp["qnorm"], c.eps)
                kN = _rms(_mm(x, lp["Wk"]), lp["knorm"], c.eps)
                ctx, k, v = paged_attention(
                    heads(q), heads(kN), heads(_mm(x, lp["Wv"])), k, v, fi,
                    pageTable, pos, start)
                out = _mm(ctx.transpose(0, 2, 1, 3).reshape(S, H * dh),
                          lp["Wo"])
                fi += 1
            x = self._close_block(lp, x, out)
        return self._logits(params, x)[:, None], k, v, delta, conv
