"""Ling-3.0 (``bailing_hybrid``) LM for the serving tier, as ONE CHIP'S
SHARE of an expert-parallel deployment: Kimi-Delta-Attention layers (KDA,
arXiv:2510.26692: a gated delta rule whose decay is per CHANNEL) beside
multi-head latent attention (MLA, arXiv:2405.04434) in one stack, a
leading dense layer and expert layers of a shared expert beside
``nExperts`` group-routed ones of which this chip holds ``expertsHeld``,
in the pre-norm block ``y = x + Mixer(RMSNorm(x))``, ``out = y +
FFN(RMSNorm(y))``, a final RMSNorm and an untied head.  Layer ``i`` (in
the PUBLISHED numbering: ``firstLayer`` is the published index of the
first layer held) is MLA where ``(i + 1) % mlaEvery == 0``, else KDA; its
FFN is dense where it is one of the first ``denseLayers`` held.

*KDA layer* on ``h`` (the normed input), ``H`` heads of ``dk = dv =
headDim``::

    q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
        (causal depthwise convolutions of K taps, zeros before the first
        real position)
    q = l2(q) / sqrt(dk), k = l2(k) a head;  beta = sigmoid(h W_b) a head
    g = lowerBound * sigmoid(exp(A_log) * (h W_f + dt_bias))   (H, dk):
        the decay a CHANNEL behind its safe gate, lowerBound <= g <= 0
    S' = Diag(exp(g_t)) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t            S (dk, dv) float32
    out = (RMSNorm_dv(o_t) * sigmoid(h W_g) a head) W_o     W_g (d, H)

The rule is :mod:`~deeplearning4j_tpu.nlp.delta`'s, which ``OlmoHybridLM``
calls with one decay a head: the step runs the recurrence against the
pool's states in place (``delta_state_step``: on one TPU a kernel that
reads a state once and writes it once), forward and prefill its chunked
form in sub-blocks that ``lowerBound`` keeps finite.

*MLA layer*: ``q = h W_q`` in heads of ``[q_nope | q_rope]`` (no query
compression); everything behind the queries — the latent row ``[c_kv |
k_r]`` a position, the unabsorbed full form, the absorbed step through
``paged_latent_attention`` — is
:class:`~deeplearning4j_tpu.nlp.latent.LatentAttention`'s, shared with
``PanguMoELM``.  Rotary positions turn the MLA layers' ``q_rope`` and
``k_r`` alone, pairs INTERLEAVED (lane ``2 i`` with ``2 i + 1``, angle
``pos * theta^(-2 i / rope)``): the program un-interleaves both (evens,
then odds) and turns halves, which leaves every score as it was.  A
token's position is its index among the REAL tokens.

*Expert layer*: ``parallel/moe.py:route_sigmoid_group_topk`` (sigmoid
scores, a correction bias in the choice only, ``nGroups`` groups of which
``groupsPerToken`` stay, ``expertsPerToken`` chosen among them), then
what ``PanguMoELM`` does: this chip adds to the shared expert's output the
part of the chosen experts it HOLDS, what the absent ones would add is
left out, no token is dropped; the step reads only the held experts that
were hit (``moe_share_step``), forward and prefill multiply by group
(``moe_share_grouped``), and three counts of the routing come back in the
columns behind the step's tokens (:data:`LingLM.stepCounters`).

What a slot keeps between steps, named by :meth:`LingLM.cacheSpec`, is
THREE things side by side in one pool: latent rows in pages (the MLA
layers), the float32 delta state ``(dk, dv)`` a head of every KDA layer,
and the last ``K - 1`` inputs of its three convolutions.

Precision: weights, residual stream and latent rows in the parameters'
dtype (bfloat16 as served); delta state, ``g``, ``beta``, the q/k norms,
the chunk's transform, router, softmax, norms, rotary angles and logits
in float32; every matmul accumulates in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.attention import CacheSpec
from deeplearning4j_tpu.nlp.delta import (delta_rule_chunked,
                                          delta_state_step, l2_normalise,
                                          short_conv_full, short_conv_step)
from deeplearning4j_tpu.nlp.latent import LatentAttention
from deeplearning4j_tpu.nlp.mamba import _mm, _rms
from deeplearning4j_tpu.nlp.served import JitByLength, ServedLM, _rope
from deeplearning4j_tpu.parallel.moe import (moe_share_counts,
                                             moe_share_grouped,
                                             moe_share_step,
                                             route_sigmoid_group_topk)

__all__ = ["LingConfig", "LingLM"]

_F32 = jnp.float32
_I32 = jnp.int32
_COUNTS = ("moe_pairs_routed", "moe_pairs_absent", "moe_experts_hit")


@dataclasses.dataclass
class LingConfig:
    vocabSize: int = 256        # rows of the embedding and the head HELD
    nLayers: int = 7
    firstLayer: int = 1         # published index of the first layer held
    denseLayers: int = 1        # leading layers held whose FFN is dense
    mlaEvery: int = 6           # published layer i is MLA where (i+1) % 6 == 0
    hiddenSize: int = 64
    nHeads: int = 4             # of the KDA and of the MLA layers alike
    headDim: int = 16           # dk = dv of a KDA head
    convKernel: int = 4         # K
    lowerBound: float = -5.0    # of g, the log decay a channel a step
    chunk: int = 64             # C of the chunked delta rule, a power of two
    kvRank: int = 32            # width of c_kv, the latent
    nopeDim: int = 16           # a head's q_nope / k_nope
    ropeDim: int = 8            # q_rope a head; the one k_r
    vDim: int = 16              # a head's v
    ffnSize: int = 128          # the dense FFN
    expertSize: int = 32        # a routed expert's, and the shared one's
    nExperts: int = 16          # routed experts the router scores
    expertsPerToken: int = 4
    expertsHeld: Tuple[int, int] = (0, 4)   # [lo, hi): this chip's share
    nGroups: int = 4            # the router's groups of nExperts / nGroups
    groupsPerToken: int = 2
    routedScale: float = 2.5
    ropeTheta: float = 6e6
    eps: float = 1e-6
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def nHeld(self) -> int:
        return self.expertsHeld[1] - self.expertsHeld[0]

    @property
    def convWidth(self) -> int:
        """Channels of the three convolutions side by side: q, k, v."""
        return 3 * self.nHeads * self.headDim

    def layerKinds(self) -> List[str]:
        return ["mla" if (self.firstLayer + i + 1) % self.mlaEvery == 0
                else "kda" for i in range(self.nLayers)]


class LingLM(LatentAttention, ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns all three kinds of cache state
    and the routing's counts, and the step form ``pagedLogits``, from
    which ``ServedLM`` builds the scheduler's fixed-shape decode step and
    admission write."""

    #: what the step returns in the columns behind its tokens (row 0), as
    #: ``PanguMoELM``'s: its own counts of the routing, then those of the
    #: prefills since the step before
    stepCounters = tuple((name, {"phase": phase})
                         for phase in ("step", "prefill")
                         for name in _COUNTS)

    def __init__(self, config: Optional[LingConfig] = None, params=None,
                 **kw):
        self.config = c = config or LingConfig(**kw)
        lo, hi = c.expertsHeld
        if not 0 <= lo < hi <= c.nExperts or c.ropeDim % 2 \
                or c.nExperts % c.nGroups:
            raise ValueError(
                f"expertsHeld {c.expertsHeld} names no share of "
                f"{c.nExperts} experts in {c.nGroups} groups, or ropeDim "
                f"{c.ropeDim} is odd")
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype, one
        small program per kind of layer; only the held experts exist."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, H, dh, f, n = (c.hiddenSize, c.nHeads, c.headDim, c.expertSize,
                          c.nHeld)
        K, std = c.convKernel, c.initializerRange

        @functools.partial(jax.jit, static_argnames=("kind", "dense"))
        def layer(key, kind, dense):
            keys = iter(jax.random.split(key, 32))
            normal = lambda *shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            conv = lambda: jax.random.uniform(
                next(keys), (K, H * dh), _F32, -K ** -0.5, K ** -0.5
            ).astype(dt)
            ones = lambda n: jnp.ones((n,), dt)
            p = {"norm1": ones(d), "norm2": ones(d)}
            if kind == "kda":
                # drawn so that the channels' decays a step spread from
                # 0.05 to 0.998 (benchmark/configs/ling3_flash.json,
                # ``assumed.kda_gate_draw``)
                A = jax.random.uniform(next(keys), (H,), _F32, 0.5, 2.0)
                p.update(
                    Wq=normal(d, H * dh), Wk=normal(d, H * dh),
                    Wv=normal(d, H * dh), Wf=normal(d, H * dh),
                    Wb=normal(d, H), Wg=normal(d, H), Wo=normal(H * dh, d),
                    convQ=conv(), convK=conv(), convV=conv(),
                    Alog=jnp.log(A).astype(dt),
                    dtBias=jax.random.uniform(
                        next(keys), (H * dh,), _F32, -6.0, 0.0).astype(dt),
                    onorm=ones(dh))
            else:
                p.update(
                    Wq=normal(d, H * (c.nopeDim + c.ropeDim)),
                    Wdkv=normal(d, c.kvRank + c.ropeDim),
                    kvnorm=ones(c.kvRank),
                    Wuk=normal(H, c.kvRank, c.nopeDim),
                    Wuv=normal(H, c.vDim, c.kvRank),
                    Wo=normal(H * c.vDim, d))
            if dense:
                p.update(Wgate=normal(d, c.ffnSize), Wup=normal(d, c.ffnSize),
                         Wdown=normal(c.ffnSize, d))
            else:
                p.update(Wr=normal(d, c.nExperts),
                         rbias=jax.random.uniform(
                             next(keys), (c.nExperts,), _F32, -0.1, 0.1),
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
                "layers": [layer(jax.random.fold_in(key, i + 1), kind,
                                 i < c.denseLayers)
                           for i, kind in enumerate(c.layerKinds())]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        one latent row a position in every MLA layer (no V pool), every
        KDA layer's delta state and convolution windows, and the counts
        of the routing that the prefills leave for the next step."""
        c = self.config
        kinds = c.layerKinds()
        nK = kinds.count("kda")
        dt = jnp.dtype(c.dtype)
        return CacheSpec(
            pagedLayers=kinds.count("mla"), kvHeads=1,
            headSize=c.kvRank + c.ropeDim, dtype=dt, latentWidth=c.kvRank,
            ropeWidth=c.ropeDim,
            slotState=(("delta", (nK, c.nHeads, c.headDim, c.headDim), _F32),
                       ("conv", (nK, c.convKernel - 1, c.convWidth), dt),
                       ("routing", (1, len(_COUNTS)), _I32)))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _rotate(self, x, p):
        """Rotary positions on INTERLEAVED pairs: the lanes are put evens
        first, then odds, and turned as halves (every score a turned
        query takes with a turned key is a sum over pairs, whatever
        order the pairs lie in)."""
        return _rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                     p, self.config.ropeTheta)

    def _queries(self, lp, h, p):
        """``(q_nope, RoPE(q_rope))`` ``(..., H, nope)``, ``(..., H,
        rope)`` float32 from ``h (..., d)`` at positions ``p (...)``."""
        c = self.config
        q = _mm(h, lp["Wq"])
        q = q.reshape(q.shape[:-1] + (c.nHeads, c.nopeDim + c.ropeDim))
        return q[..., :c.nopeDim], self._rotate(q[..., c.nopeDim:],
                                                p[..., None])

    def _gates(self, lp, h):
        """``beta (..., H)`` and ``g = log decay (..., H, dk)`` from ``h
        (..., d)``, float32: ``lowerBound <= g <= 0``."""
        c = self.config
        f = lambda n: lp[n].astype(_F32)
        z = (_mm(h, lp["Wf"]) + f("dtBias")).reshape(
            h.shape[:-1] + (c.nHeads, c.headDim))
        g = c.lowerBound * jax.nn.sigmoid(jnp.exp(f("Alog"))[:, None] * z)
        return jax.nn.sigmoid(_mm(h, lp["Wb"])), g

    def _heads(self, q, k, v):
        """The three convolutions' outputs ``(..., H dk)`` through their
        SiLU and into heads ``(..., H, dk)``, ``q`` and ``k`` normalised."""
        c = self.config
        split = lambda a: jax.nn.silu(a).reshape(
            a.shape[:-1] + (c.nHeads, c.headDim))
        return (l2_normalise(split(q)) * c.headDim ** -0.5,
                l2_normalise(split(k)), split(v))

    def _kda_out(self, lp, o, h):
        """``(RMSNorm_dv(o) a head * sigmoid(h W_g) a head) W_o``."""
        o = _rms(o, lp["onorm"], self.config.eps) \
            * jax.nn.sigmoid(_mm(h, lp["Wg"]))[..., None]
        return _mm(o.reshape(o.shape[:-2] + (-1,)), lp["Wo"])

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
        idx, w = route_sigmoid_group_topk(
            h, lp["Wr"], lp["rbias"], c.expertsPerToken, c.nGroups,
            c.groupsPerToken, c.routedScale)
        experts = (lp["Eg"], lp["Eu"], lp["Ed"], lo)
        if grouped:
            # the held pairs a token expects, and a token's worth of room:
            # one pass, unless the router leans on this chip's groups
            rows = h.shape[0] * (
                1 + -(-c.expertsPerToken * c.nHeld // c.nExperts))
            routed = moe_share_grouped(h, idx, w, *experts, real,
                                       passRows=rows)
        else:
            routed = moe_share_step(h, idx, w, *experts, real)
        return gated("Sgate", "Sup", "Sdown") + routed, \
            moe_share_counts(idx, lo, c.nHeld, real)

    def _logits(self, params, x):
        return _mm(_rms(x, params["normf"], self.config.eps), params["head"])

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill
    # ------------------------------------------------------------------
    def _kda_full(self, lp, h, realF):
        """The KDA mixer over whole LEFT-padded sequences ``h (b, T, d)``:
        ``(out (b, T, d), S_T (b, H, dk, dv), the last K - 1 convolution
        inputs (b, K - 1, 3 H dk))``.  A pad position changes nothing: its
        convolution inputs are zero (so its q, k, v are), its ``beta`` and
        its ``g`` are 0."""
        c = self.config
        # q, k and v one after the other (see OlmoHybridLM._run_full)
        convolved = lambda w, taps: short_conv_full(
            _mm(h, lp[w]) * realF, lp[taps])
        (q, tq), (k, tk), (v, tv) = (
            convolved("Wq", "convQ"), convolved("Wk", "convK"),
            convolved("Wv", "convV"))
        q, k, v = self._heads(q, k, v)
        beta, g = self._gates(lp, h)
        with jax.named_scope("kda_chunked"):
            o, S = delta_rule_chunked(q, k, v, beta * realF,
                                      g * realF[..., None], c.chunk,
                                      lowerBound=c.lowerBound)
        return self._kda_out(lp, o, h), S, \
            jnp.concatenate([tq, tk, tv], axis=-1)

    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last layer's output and what a decode
        continues from: the MLA layers' latent rows ``(mla layers, b, 1,
        T, W)``, the KDA layers' end states and convolution windows, and
        the routing's counts over the real tokens ``(3,)``."""
        c = self.config
        b, T = tokens.shape
        at = jnp.arange(T, dtype=_I32)[None, :]
        real = at >= start[:, None]                          # (b, T)
        realF = real.astype(_F32)[..., None]
        p = jnp.maximum(at - start[:, None], 0)
        x = params["emb"][tokens]
        cd = x.dtype
        spec = self.cacheSpec()
        rows = jnp.zeros((spec.pagedLayers, b, 1, T, spec.rowWidth), cd)
        (_, dShape, _), (_, cShape, _), _ = spec.slotState
        delta = jnp.zeros(dShape[:1] + (b,) + dShape[1:], _F32)
        conv = jnp.zeros(cShape[:1] + (b,) + cShape[1:], cd)
        counts = jnp.zeros((len(_COUNTS),), _I32)
        # the stream is written out after every add (see
        # OlmoHybridLM._run_full)
        hold = jax.lax.optimization_barrier
        ki = mi = 0
        for kind, lp in zip(c.layerKinds(), params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            if kind == "kda":
                out, S, win = self._kda_full(lp, h, realF)
                delta = delta.at[ki].set(S)
                conv = conv.at[ki].set(win.astype(cd))
                ki += 1
            else:
                qn, qr = self._queries(lp, h, p)
                row = self._latent_row(lp, h, p, cd)
                rows = rows.at[mi, :, 0].set(row)
                out = _mm(self._latent_full(lp, qn, qr, row, start),
                          lp["Wo"])
                mi += 1
            y = hold(x + out.astype(cd))
            ff, n = self._ffn(lp, _rms(y, lp["norm2"], c.eps
                                       ).reshape(b * T, -1),
                              real.reshape(-1), grouped=True)
            counts = counts + n
            x = hold(y + ff.reshape(b, T, -1).astype(cd))
        return x, (rows, delta, conv), counts

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
        """``(last logits (b, vocab), rowStack, delta, conv, counts)``:
        the latent rows in :func:`paged_rows_write`'s form ``(mla layers,
        b, 1, t, W)``, the slot state ``(kda layers, b, ...)`` and the
        routing's counts ``(1, b, 3)`` in the pool's order (the whole
        batch's in every row: the scheduler prefills one sequence at a
        time)."""
        def run(params, tokens, start):
            x, state, counts = self._run_full(params, tokens, start)
            b = tokens.shape[0]
            return (self._logits(params, x[:, -1]),) + state + (
                jnp.broadcast_to(counts, (1, b) + counts.shape),)
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def _kda_step(self, lp, h, delta, conv, ki, active):
        """One token a slot through KDA layer ``ki``: ``h (S, d)`` against
        the pool's states ``delta (layers, slots, H, dk, dv)`` and windows
        ``conv (layers, slots, K - 1, 3 H dk)``.  Returns ``(out (S, d),
        delta, conv)``; a slot that is not ``active (S,)`` keeps its
        state.  Everything that touches the state (the window's shift, the
        decay, the delta, the read, the write back) carries the scope
        ``kda_step`` into the compiled step."""
        c = self.config
        qkv = jnp.concatenate([_mm(h, lp["Wq"]), _mm(h, lp["Wk"]),
                               _mm(h, lp["Wv"])], axis=-1)
        beta, g = self._gates(lp, h)
        with jax.named_scope("kda_step"):
            taps = jnp.concatenate(
                [lp["convQ"], lp["convK"], lp["convV"]], axis=-1)
            u, win = short_conv_step(conv[ki], qkv, taps)
            n = c.nHeads * c.headDim
            q, k, v = self._heads(u[:, :n], u[:, n:2 * n], u[:, 2 * n:])
            delta, o = delta_state_step(delta, ki, q, k, v, beta,
                                        jnp.exp(g), active)
            conv = conv.at[ki].set(jnp.where(
                active[:, None, None], win.astype(conv.dtype), conv[ki]))
        return self._kda_out(lp, o, h), delta, conv

    def pagedLogits(self, params, rows, delta, conv, routing, toks,
                    pageTable, pos, start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays, the MLA layers ABSORBED: ``((S, 1, vocab) logits, rows,
        delta, conv, routing, counts (6,))``.  A slot whose ``pos`` is 0
        holds no sequence (or is deferred a round): its row lands on the
        scratch page through its zeroed page table, its recurrent state
        is left as it is and it is not counted.  ``counts`` are this
        step's three counts of the routing, then the three that the
        prefills since the last step left in ``routing``, which comes
        back zeroed."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "a recurrent state advances one token a step: speculative "
                "verification (tq > 1) would need its roll-back")
        active = pos > 0
        p = jnp.maximum(pos - start, 0)
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        counts = jnp.zeros((len(_COUNTS),), _I32)
        ki = mi = 0
        for kind, lp in zip(c.layerKinds(), params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            if kind == "kda":
                out, delta, conv = self._kda_step(lp, h, delta, conv, ki,
                                                   active)
                ki += 1
            else:
                qn, qr = self._queries(lp, h, p)              # (S, H, .)
                o, rows = self._latent_step(
                    lp, qn, qr, self._latent_row(lp, h, p, cd), rows, mi,
                    pageTable, pos, start)
                out = _mm(o.reshape(S, c.nHeads * c.vDim), lp["Wo"])
                mi += 1
            y = x + out.astype(cd)
            ff, n = self._ffn(lp, _rms(y, lp["norm2"], c.eps), active,
                              grouped=False)
            counts = counts + n
            x = y + ff.astype(cd)
        left = jnp.sum(routing, axis=(0, 1)).astype(_I32)
        return (self._logits(params, x)[:, None], rows, delta, conv,
                jnp.zeros_like(routing), jnp.concatenate([counts, left]))
