"""Pangu-Ultra-MoE LM for the serving tier, as ONE CHIP'S SHARE of an
expert-parallel deployment: multi-head latent attention (arXiv:2405.04434)
with rotary positions, a leading dense layer and expert layers of a
shared expert beside ``nExperts`` routed ones of which this chip holds
``expertsHeld``, in the sandwich-norm block of Pangu Ultra
(arXiv:2504.07866, arXiv:2505.04519): ``y = x + RMSNorm_2(Attn(RMSNorm_1
(x)))``, ``out = y + RMSNorm_4(FFN(RMSNorm_3(y)))``, a final RMSNorm and
an untied head.

*Attention.*  ``c_q = RMSNorm(h W_dq)``; ``q = c_q W_uq`` in heads of
``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_dkv`` with ``c_kv =
RMSNorm(c_kv)`` and ONE rotated ``k_r = RoPE(k_r)`` for all heads;
``k_nope = c_kv W_uk``, ``v = c_kv W_uv`` a head; ``s = (q_nope . k_nope
+ RoPE(q_rope) . k_r) / sqrt(nope + rope)``.  It has two arithmetic
forms.  Forward and prefill run it as written (unabsorbed: keys and
values of every position are formed).  The decode step runs it ABSORBED:
``q~ = q_nope W_uk^T`` a head, scores ``q~ . c_kv + q_rope . k_r``
against the cached rows themselves, ``o = (softmax(s) c_kv) W_uv``; so
what a position keeps is one LATENT ROW ``[c_kv | k_r]`` (after norm and
rotation), from which keys and values both come: the model's
``cacheSpec()`` names it, the scheduler's pool holds one array of them,
and :func:`~deeplearning4j_tpu.nn.conf.attention.paged_latent_attention`
reads it (on one TPU the kernel over the live pages).  Everything behind
the queries (the row, both forms, the flash kernel for long prefills) is
:class:`~deeplearning4j_tpu.nlp.latent.LatentAttention`'s, which
``LingLM`` inherits too; the compressed query is this model's own.

*Rotary positions* pair lane ``i`` with lane ``i + rope / 2`` and turn
the pair by ``pos * theta^(-2 i / rope)``; a token's position is its
index among the REAL tokens: in a left-padded bucket ``p - start``, in
the step ``pos - start``.  A pad has no position and is no key.

*Expert layer.*  The router scores all ``nExperts`` in float32
(``parallel/moe.py:route_sigmoid_topk``), the ``expertsPerToken``
largest are chosen, and this chip adds to the shared expert's output the
part of the chosen experts it HOLDS; what the absent ones would have
added is left out (the deployment's exchange would bring it), and the
partial result goes on.  No token is dropped.  The step reads only the
held experts that a live slot's token chose (``moe_share_step``: on one
TPU a kernel over the hit experts, their ids scalar-prefetched; on the
CPU or several devices every held expert over every slot,
``moe_share_dense``); forward and prefill sort the pairs by expert and
multiply by group (``moe_share_grouped``).
Three counts of the routing are taken on the device in both
(:data:`PanguMoELM.stepCounters`) and come back in the columns behind
the step's tokens.

Precision: weights, residual stream and latent rows in the parameters'
dtype (bfloat16 as served); router, softmax, norms, rotary angles and
logits in float32; every matmul accumulates in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.attention import CacheSpec
from deeplearning4j_tpu.nlp.latent import LatentAttention
from deeplearning4j_tpu.nlp.mamba import _mm, _rms
from deeplearning4j_tpu.nlp.served import JitByLength, ServedLM
from deeplearning4j_tpu.parallel.moe import (moe_share_counts,
                                             moe_share_grouped,
                                             moe_share_step,
                                             route_sigmoid_topk)

__all__ = ["PanguMoEConfig", "PanguMoELM"]

_F32 = jnp.float32
_I32 = jnp.int32
_COUNTS = ("moe_pairs_routed", "moe_pairs_absent", "moe_experts_hit")


@dataclasses.dataclass
class PanguMoEConfig:
    vocabSize: int = 256        # rows of the embedding and the head HELD
    nLayers: int = 3
    denseLayers: int = 1        # leading layers whose FFN is dense
    hiddenSize: int = 64
    nHeads: int = 4
    qRank: int = 32             # width of c_q
    kvRank: int = 32            # width of c_kv, the latent
    nopeDim: int = 16           # a head's q_nope / k_nope
    ropeDim: int = 8            # q_rope a head; the one k_r
    vDim: int = 16              # a head's v
    ffnSize: int = 128          # the dense FFN
    expertSize: int = 32        # a routed expert's, and the shared one's
    nExperts: int = 16          # routed experts the router scores
    expertsPerToken: int = 4
    expertsHeld: Tuple[int, int] = (0, 4)   # [lo, hi): this chip's share
    routedScale: float = 2.5
    ropeTheta: float = 25.6e6
    eps: float = 1e-5
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def nHeld(self) -> int:
        return self.expertsHeld[1] - self.expertsHeld[0]


class PanguMoELM(LatentAttention, ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns the latent rows and the
    routing's counts, and the step form ``pagedLogits``, from which
    ``ServedLM`` builds the scheduler's fixed-shape decode step and
    admission write."""

    #: what the step returns in the columns behind its tokens (row 0),
    #: for the batcher to add to ``serving_metrics()``: its own counts of
    #: the routing, then those of the prefills since the step before
    stepCounters = tuple((name, {"phase": phase})
                         for phase in ("step", "prefill")
                         for name in _COUNTS)

    def __init__(self, config: Optional[PanguMoEConfig] = None,
                 params=None, **kw):
        self.config = c = config or PanguMoEConfig(**kw)
        lo, hi = c.expertsHeld
        if not 0 <= lo < hi <= c.nExperts or c.ropeDim % 2:
            raise ValueError(
                f"expertsHeld {c.expertsHeld} names no share of "
                f"{c.nExperts} experts, or ropeDim {c.ropeDim} is odd")
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype, one
        small program per kind of layer; only the held experts exist."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, H, f, n = c.hiddenSize, c.nHeads, c.expertSize, c.nHeld
        std = c.initializerRange

        @functools.partial(jax.jit, static_argnames=("dense",))
        def layer(key, dense):
            keys = iter(jax.random.split(key, 20))
            normal = lambda *shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            ones = lambda n: jnp.ones((n,), dt)
            p = {"norm1": ones(d), "norm2": ones(d), "norm3": ones(d),
                 "norm4": ones(d), "qnorm": ones(c.qRank),
                 "kvnorm": ones(c.kvRank),
                 "Wdq": normal(d, c.qRank),
                 "Wuq": normal(c.qRank, H * (c.nopeDim + c.ropeDim)),
                 "Wdkv": normal(d, c.kvRank + c.ropeDim),
                 # head-major, as the step's matmuls a head read them:
                 # W_uk (rank, nope) and W_uv^T (v, rank) a head, each
                 # contracted over its minor dimension
                 "Wuk": normal(H, c.kvRank, c.nopeDim),
                 "Wuv": normal(H, c.vDim, c.kvRank),
                 "Wo": normal(H * c.vDim, d)}
            if dense:
                p.update(Wgate=normal(d, c.ffnSize), Wup=normal(d, c.ffnSize),
                         Wdown=normal(c.ffnSize, d))
            else:
                p.update(Wr=normal(d, c.nExperts),
                         Sgate=normal(d, f), Sup=normal(d, f),
                         Sdown=normal(f, d), Eg=normal(n, d, f),
                         Eu=normal(n, d, f), Ed=normal(n, f, d))
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
                "layers": [layer(jax.random.fold_in(key, i + 1),
                                 i < c.denseLayers)
                           for i in range(c.nLayers)]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        one latent row a position in every layer (no V pool), and beside
        them the counts of the routing that the prefills leave for the
        next step to return."""
        c = self.config
        return CacheSpec(
            pagedLayers=c.nLayers, kvHeads=1, headSize=c.kvRank + c.ropeDim,
            dtype=jnp.dtype(c.dtype), latentWidth=c.kvRank,
            ropeWidth=c.ropeDim,
            slotState=(("routing", (1, len(_COUNTS)), _I32),))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _queries(self, lp, h, p):
        """``(q_nope, RoPE(q_rope))`` ``(..., H, nope)``, ``(..., H,
        rope)`` float32 from ``h (..., d)`` at positions ``p (...)``."""
        c = self.config
        q = _mm(_rms(_mm(h, lp["Wdq"]), lp["qnorm"], c.eps), lp["Wuq"])
        q = q.reshape(q.shape[:-1] + (c.nHeads, c.nopeDim + c.ropeDim))
        return q[..., :c.nopeDim], self._rotate(q[..., c.nopeDim:],
                                                p[..., None])

    def _ffn(self, lp, h, real, grouped: bool):
        """``(FFN(h), counts)`` for ``h (T, d)`` float32: the dense FFN,
        or the shared expert plus this chip's part of the routed ones;
        ``counts`` of the routing over the ``real (T,)`` tokens (zeros
        for a dense layer)."""
        c = self.config
        gated = lambda g, u, dn: _mm(
            jax.nn.silu(_mm(h, lp[g])) * _mm(h, lp[u]), lp[dn])
        if "Wgate" in lp:
            return gated("Wgate", "Wup", "Wdown"), \
                jnp.zeros((len(_COUNTS),), _I32)
        lo = c.expertsHeld[0]
        idx, w = route_sigmoid_topk(h, lp["Wr"], c.expertsPerToken,
                                    c.routedScale)
        experts = (lp["Eg"], lp["Eu"], lp["Ed"], lo)
        routed = (moe_share_grouped if grouped else moe_share_step)(
            h, idx, w, *experts, real)
        return gated("Sgate", "Sup", "Sdown") + routed, \
            moe_share_counts(idx, lo, c.nHeld, real)

    def _logits(self, params, x):
        return _mm(_rms(x, params["normf"], self.config.eps), params["head"])

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill (attention unabsorbed)
    # ------------------------------------------------------------------
    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last layer's output, every layer's latent
        rows as the step will read them ``(L, b, 1, T, W)`` and the
        routing's counts over the real tokens ``(3,)``."""
        c = self.config
        b, T = tokens.shape
        H = c.nHeads
        at = jnp.arange(T, dtype=_I32)[None, :]
        real = at >= start[:, None]                          # (b, T)
        p = jnp.maximum(at - start[:, None], 0)
        x = params["emb"][tokens]
        cd = x.dtype
        rows = jnp.zeros((c.nLayers, b, 1, T, self.cacheSpec().rowWidth), cd)
        counts = jnp.zeros((len(_COUNTS),), _I32)
        hold = jax.lax.optimization_barrier
        for li, lp in enumerate(params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            qn, qr = self._queries(lp, h, p)
            row = self._latent_row(lp, h, p, cd)
            rows = rows.at[li, :, 0].set(row)
            o = self._latent_full(lp, qn, qr, row, start)
            # the stream is written out after every add (see
            # OlmoHybridLM._run_full)
            y = hold(x + _rms(_mm(o, lp["Wo"]), lp["norm2"],
                              c.eps).astype(cd))
            ff, n = self._ffn(lp, _rms(y, lp["norm3"], c.eps
                                       ).reshape(b * T, -1),
                              real.reshape(-1), grouped=True)
            counts = counts + n
            x = hold(y + _rms(ff, lp["norm4"], c.eps
                              ).reshape(b, T, -1).astype(cd))
        return x, rows, counts

    @functools.cached_property
    def _fwd(self):
        def run(params, tokens):
            start = jnp.zeros((tokens.shape[0],), _I32)
            return self._logits(params, self._run_full(params, tokens,
                                                       start)[0])
        return jax.jit(run)

    def forward(self, tokens) -> jax.Array:
        """Full causal forward: (b, t) int32 -> (b, t, vocab) float32."""
        return self._fwd(self.params, jnp.asarray(tokens, _I32))

    @functools.cached_property
    def _prefillRawFn(self):
        """``(last logits (b, vocab), rowStack, counts)``: the latent rows
        in :func:`paged_rows_write`'s form ``(layers, b, 1, t, W)`` and
        the routing's counts ``(1, b, 3)`` in the pool's order (the whole
        batch's in every row: the scheduler prefills one sequence at a
        time)."""
        def run(params, tokens, start):
            x, rows, counts = self._run_full(params, tokens, start)
            b = tokens.shape[0]
            # the counts ride as slot state: (1 layer, b, 3), the batch
            # row's own where there is one row (the scheduler's case)
            return (self._logits(params, x[:, -1]), rows,
                    jnp.broadcast_to(counts, (1, b) + counts.shape))
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, rows, routing, toks, pageTable, pos,
                    start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays, attention ABSORBED: ``((S, 1, vocab) logits, rows,
        routing, counts (6,))``.  A slot whose ``pos`` is 0 holds no
        sequence (or is deferred a round): its row lands on the scratch
        page through its zeroed page table and it is not counted.
        ``counts`` are this step's three counts of the routing, then the
        three that the prefills since the last step left in ``routing``,
        which comes back zeroed."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "the step takes one token a slot: speculative "
                "verification (tq > 1) would need a position a query")
        H = c.nHeads
        active = pos > 0
        p = jnp.maximum(pos - start, 0)
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        counts = jnp.zeros((len(_COUNTS),), _I32)
        for li, lp in enumerate(params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            qn, qr = self._queries(lp, h, p)                  # (S, H, .)
            o, rows = self._latent_step(
                lp, qn, qr, self._latent_row(lp, h, p, cd), rows, li,
                pageTable, pos, start)
            y = x + _rms(_mm(o.reshape(S, H * c.vDim), lp["Wo"]),
                         lp["norm2"], c.eps).astype(cd)
            ff, n = self._ffn(lp, _rms(y, lp["norm3"], c.eps), active,
                              grouped=False)
            counts = counts + n
            x = y + _rms(ff, lp["norm4"], c.eps).astype(cd)
        left = jnp.sum(routing, axis=(0, 1)).astype(_I32)
        return (self._logits(params, x)[:, None], rows,
                jnp.zeros_like(routing), jnp.concatenate([counts, left]))
