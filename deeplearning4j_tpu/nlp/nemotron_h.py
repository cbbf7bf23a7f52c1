"""Nemotron-H (``nemotron_h``; Nemotron-H, arXiv:2504.03624, with the
LatentMoE of the Nemotron 3 description) LM for the serving tier, as ONE
CHIP'S SHARE of an expert-parallel deployment.  The stack is read from a
pattern string, one character a block, and a block holds ONE sub-layer::

    x = x + Sub_i(RMSNorm_i(x))      Sub_i by pattern[i]:
        M  Mamba-2 (SSD)    *  attention    E  expert layer (LatentMoE)

a final RMSNorm and an untied head; no positions are added anywhere (the
state-space layers carry order).  Every other served model's block is a
mixer THEN a feed-forward part.

*M*: :mod:`~deeplearning4j_tpu.nlp.mamba`'s Mamba-2 (``ssd_full`` over a
prefill's chunks, ``ssd_step`` against the pool's states): one decay a
head, ``B`` and ``C`` of ``nGroups`` groups, a float32 state ``(H, P,
N)`` a layer a slot, a gated norm over groups.

*\\**: grouped-query attention without bias and WITHOUT rotary positions:
``q, k, v = h W_q, h W_k, h W_v``, causal softmax of ``q . k / sqrt(dh)``
over the real positions, query head ``j`` on KV head ``j // (H / KV)``;
the step reads the slot's pages through ``paged_attention``.

*E*: ``s = sigmoid(h W_r)`` over all ``routerWidth`` experts in float32;
the ``expertsPerToken`` largest of ``s + b`` are chosen (``b`` the
correction bias, in the choice only:
``parallel/moe.py:route_sigmoid_group_topk`` with one group), ``w =
s_chosen / sum(s_chosen) * routedScale``.  The routed experts work in a
LATENT: ``u = h W_down`` (``latentSize`` wide), ``r = sum_e w_e W2_e
relu(W1_e u)²`` over the chosen experts this chip HOLDS (``expertsHeld``;
what the absent ones would add is left out, no token is dropped),
``FFN(h) = r W_up + W2_s relu(W1_s h)²``, the shared expert at full
width.  The step reads only the held experts that were hit
(``moe_share_step``), forward and prefill multiply by group
(``moe_share_grouped``); three counts of the routing come back in the
columns behind the step's tokens (:data:`NemotronHLM.stepCounters`).  In
a trace the block's three parts lie under the scope ``latent_moe``, the
step's experts under ``moe_share_step`` inside it.

What a slot keeps between steps, named by :meth:`NemotronHLM.cacheSpec`:
K/V rows in pages (the ``*`` blocks), and for every ``M`` block the
float32 state and the convolution's last ``K - 1`` inputs.

Precision: weights, residual stream and K/V in the parameters' dtype
(bfloat16 as served); the SSD state, ``dt``, ``exp``, the convolution,
every norm, the router and the softmax in float32; every matmul takes
its input in the weight's dtype and accumulates in float32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.attention import (CacheSpec,
                                                  paged_attention)
from deeplearning4j_tpu.nlp.mamba import _mm, _rms, ssd_full, ssd_step
from deeplearning4j_tpu.nlp.served import (JitByLength, ServedLM,
                                           attend_full)
from deeplearning4j_tpu.parallel.moe import (moe_share_counts,
                                             moe_share_grouped,
                                             moe_share_step, relu2,
                                             route_sigmoid_group_topk)

__all__ = ["NemotronHConfig", "NemotronHLM"]

_F32 = jnp.float32
_I32 = jnp.int32
_COUNTS = ("moe_pairs_routed", "moe_pairs_absent", "moe_experts_hit")


@dataclasses.dataclass
class NemotronHConfig:
    vocabSize: int = 96         # rows of the embedding and the head HELD
    pattern: str = "MEM*E"      # hybrid_override_pattern of the blocks held
    firstBlock: int = 0         # published index of the first block held
    hiddenSize: int = 64
    nHeads: int = 4             # attention's query heads
    nKvHeads: int = 2
    headDim: int = 16
    mambaHeads: int = 16        # H
    mambaHeadDim: int = 8       # P; d_in = H P
    nGroups: int = 2            # G: groups of B and C, and of the gated norm
    stateSize: int = 16         # N
    convKernel: int = 4         # K
    chunk: int = 8              # positions a chunk of the SSD prefill
    latentSize: int = 32        # the routed experts' input and output
    expertSize: int = 48        # a routed expert's width
    sharedSize: int = 96        # the shared expert's, at full width
    routerWidth: int = 16       # routed experts the router scores
    expertsPerToken: int = 6
    expertsHeld: Tuple[int, int] = (0, 4)   # [lo, hi): this chip's share
    routerGroups: int = 1       # n_group
    groupsPerToken: int = 1     # topk_group
    routedScale: float = 5.0
    eps: float = 1e-5
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def nHeld(self) -> int:
        return self.expertsHeld[1] - self.expertsHeld[0]

    @property
    def innerSize(self) -> int:
        return self.mambaHeads * self.mambaHeadDim

    @property
    def convWidth(self) -> int:
        """Channels of the convolution: x, B and C side by side."""
        return self.innerSize + 2 * self.nGroups * self.stateSize


class NemotronHLM(ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns every kind of cache state and
    the routing's counts, and the step form ``pagedLogits``, from which
    ``ServedLM`` builds the scheduler's fixed-shape decode step and
    admission write."""

    #: what the step returns in the columns behind its tokens (row 0), as
    #: ``LingLM``'s: its own counts of the routing, then those of the
    #: prefills since the step before
    stepCounters = tuple((name, {"phase": phase})
                         for phase in ("step", "prefill")
                         for name in _COUNTS)

    def __init__(self, config: Optional[NemotronHConfig] = None,
                 params=None, **kw):
        self.config = c = config or NemotronHConfig(**kw)
        lo, hi = c.expertsHeld
        if not 0 <= lo < hi <= c.routerWidth or set(c.pattern) - set("M*E") \
                or c.mambaHeads % c.nGroups or c.nHeads % c.nKvHeads:
            raise ValueError(
                f"expertsHeld {c.expertsHeld} names no share of "
                f"{c.routerWidth} experts, the pattern {c.pattern!r} holds "
                "another block than M, * and E, or the heads do not divide "
                "into their groups")
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype, one
        small program per kind of block; only the held experts exist."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, H, dIn, cw = c.hiddenSize, c.mambaHeads, c.innerSize, c.convWidth
        K, std, n = c.convKernel, c.initializerRange, c.nHeld

        @functools.partial(jax.jit, static_argnames=("kind",))
        def block(key, kind):
            keys = iter(jax.random.split(key, 16))
            normal = lambda *shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            uniform = lambda shape, lo, hi, t=dt: jax.random.uniform(
                next(keys), shape, _F32, lo, hi).astype(t)
            # an expert at a time under vmap: one draw of all the held
            # experts' (n, 1024, 2688) does not compile for a v5e in ten
            # minutes (the compiler tries to fit its 17 GB of temporaries)
            experts = lambda *shape: jax.vmap(lambda k: (
                std * jax.random.normal(k, shape, _F32)).astype(dt))(
                    jax.random.split(next(keys), n))
            p = {"norm": jnp.ones((d,), dt)}
            if kind == "M":
                # the initialiser's draw: dt ~ logU[1e-3, 1e-1] through the
                # inverse softplus, A ~ U[1, 16], D = 1
                dtv = jnp.exp(uniform((H,), math.log(1e-3), math.log(1e-1),
                                      _F32))
                p.update(
                    Win=normal(d, dIn + cw + H),
                    convW=uniform((K, cw), -K ** -0.5, K ** -0.5),
                    convB=uniform((cw,), -K ** -0.5, K ** -0.5),
                    dtBias=(dtv + jnp.log(-jnp.expm1(-dtv))).astype(dt),
                    Alog=jnp.log(uniform((H,), 1.0, 16.0, _F32)).astype(dt),
                    D=jnp.ones((H,), dt), gnorm=jnp.ones((dIn,), dt),
                    Wout=normal(dIn, d))
            elif kind == "*":
                p.update(Wq=normal(d, c.nHeads * c.headDim),
                         Wk=normal(d, c.nKvHeads * c.headDim),
                         Wv=normal(d, c.nKvHeads * c.headDim),
                         Wo=normal(c.nHeads * c.headDim, d))
            else:
                p.update(Wr=std * jax.random.normal(
                             next(keys), (d, c.routerWidth), _F32),
                         rbias=uniform((c.routerWidth,), -0.01, 0.01, _F32),
                         Wdown=normal(d, c.latentSize),
                         Wup=normal(c.latentSize, d),
                         S1=normal(d, c.sharedSize),
                         S2=normal(c.sharedSize, d),
                         E1=experts(c.latentSize, c.expertSize),
                         E2=experts(c.expertSize, c.latentSize))
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
                "layers": [block(jax.random.fold_in(key, c.firstBlock + i
                                                    + 1), kind)
                           for i, kind in enumerate(c.pattern)]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each block keeps between steps, for the scheduler's pool:
        K/V pages for the ``*`` blocks, every ``M`` block's state and
        convolution window, and the counts of the routing that the
        prefills leave for the next step (the counts' carry last)."""
        c = self.config
        nM = c.pattern.count("M")
        dt = jnp.dtype(c.dtype)
        return CacheSpec(
            pagedLayers=c.pattern.count("*"), kvHeads=c.nKvHeads,
            headSize=c.headDim, dtype=dt,
            slotState=(("ssm", (nM, c.mambaHeads, c.mambaHeadDim,
                                c.stateSize), _F32),
                       ("conv", (nM, c.convKernel - 1, c.convWidth), dt),
                       ("routing", (1, len(_COUNTS)), _I32)))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _ssd(self) -> dict:
        c = self.config
        return dict(H=c.mambaHeads, P=c.mambaHeadDim, G=c.nGroups,
                    N=c.stateSize, eps=c.eps)

    def _experts(self, lp, h, real, grouped: bool):
        """``(FFN(h), counts)`` for ``h (T, d)`` float32: the shared
        expert at full width plus, through the latent, this chip's part
        of the routed ones; ``counts`` of the routing over the ``real
        (T,)`` tokens."""
        c = self.config
        lo = c.expertsHeld[0]
        idx, w = route_sigmoid_group_topk(
            h, lp["Wr"], lp["rbias"], c.expertsPerToken, c.routerGroups,
            c.groupsPerToken, c.routedScale)
        with jax.named_scope("latent_moe"):
            u = _mm(h, lp["Wdown"])
            if grouped:
                # the held pairs a token expects, and a token's worth of
                # room: one pass, unless the router leans on this chip
                rows = h.shape[0] * (
                    1 + -(-c.expertsPerToken * c.nHeld // c.routerWidth))
                r = moe_share_grouped(u, idx, w, None, lp["E1"], lp["E2"],
                                      lo, real, passRows=rows, act=relu2)
            else:
                with jax.named_scope("moe_share_step"):
                    r = moe_share_step(u, idx, w, None, lp["E1"], lp["E2"],
                                       lo, real, act=relu2)
            routed = _mm(r, lp["Wup"])
        return _mm(relu2(_mm(h, lp["S1"])), lp["S2"]) + routed, \
            moe_share_counts(idx, lo, c.nHeld, real)

    def _logits(self, params, x):
        return _mm(_rms(x, params["normf"], self.config.eps), params["head"])

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill
    # ------------------------------------------------------------------
    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last block's output and what a decode
        continues from: the ``*`` blocks' K and V rows ``(layers, b, 1,
        T, KV dh)``, the ``M`` blocks' end states and convolution
        windows, and the routing's counts over the real tokens ``(3,)``."""
        c = self.config
        b, T = tokens.shape
        real = jnp.arange(T, dtype=_I32)[None, :] >= start[:, None]
        realF = real.astype(_F32)[..., None]                 # (b, T, 1)
        x = params["emb"][tokens]
        cd = x.dtype
        pagedK, pagedV, ssm, conv = [], [], [], []
        counts = jnp.zeros((len(_COUNTS),), _I32)
        # the stream is written out after every add (see
        # OlmoHybridLM._run_full)
        hold = jax.lax.optimization_barrier
        for kind, lp in zip(c.pattern, params["layers"]):
            h = _rms(x, lp["norm"], c.eps)
            if kind == "M":
                out, S, tail = ssd_full(lp, h, realF, K=c.convKernel,
                                        chunk=c.chunk, **self._ssd())
                ssm.append(S)
                conv.append(tail.astype(cd))
            elif kind == "*":
                q, kR, vR = (_mm(h, lp[w]).astype(cd)
                             for w in ("Wq", "Wk", "Wv"))
                pagedK.append(kR)
                pagedV.append(vR)
                out = _mm(attend_full(q, kR, vR, start, nHeads=c.nHeads,
                                      nKvHeads=c.nKvHeads), lp["Wo"])
            else:
                out, n = self._experts(lp, h.reshape(b * T, -1),
                                       real.reshape(-1), grouped=True)
                out = out.reshape(b, T, -1)
                counts = counts + n
            x = hold(x + out.astype(cd))
        return x, (jnp.stack(pagedK)[:, :, None], jnp.stack(pagedV)[:, :, None],
                   jnp.stack(ssm), jnp.stack(conv)), counts

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
        """``(last logits (b, vocab), kStack, vStack, ssm, conv,
        counts)``: the paged stacks in :func:`paged_rows_write`'s form
        ``(* blocks, b, 1, t, KV dh)``, the slot state ``(M blocks, b,
        ...)`` and the routing's counts ``(1, b, 3)`` in the pool's order
        (the whole batch's in every row: the scheduler prefills one
        sequence at a time)."""
        def run(params, tokens, start):
            x, state, counts = self._run_full(params, tokens, start)
            b = tokens.shape[0]
            return (self._logits(params, x[:, -1]),) + state + (
                jnp.broadcast_to(counts, (1, b) + counts.shape),)
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, k, v, ssm, conv, routing, toks, pageTable,
                    pos, start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays: ``((S, 1, vocab) logits, k, v, ssm, conv, routing, counts
        (6,))``.  A slot whose ``pos`` is 0 holds no sequence (or is
        deferred a round): its paged write lands on the scratch page
        through its zeroed page table, its recurrent state is left as it
        is and it is not counted.  ``counts`` are this step's three
        counts of the routing, then the three that the prefills since the
        last step left in ``routing``, which comes back zeroed."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "a recurrent state advances one token a step: speculative "
                "verification (tq > 1) would need its roll-back")
        active = pos > 0
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        heads = lambda a: a.reshape(S, 1, -1, c.headDim).transpose(0, 2, 1, 3)
        counts = jnp.zeros((len(_COUNTS),), _I32)
        mi = ai = 0
        for kind, lp in zip(c.pattern, params["layers"]):
            h = _rms(x, lp["norm"], c.eps)
            if kind == "M":
                out, ssm, conv = ssd_step(lp, h, ssm, conv, mi, active,
                                          **self._ssd())
                mi += 1
            elif kind == "*":
                ctx, k, v = paged_attention(
                    heads(_mm(h, lp["Wq"])), heads(_mm(h, lp["Wk"])),
                    heads(_mm(h, lp["Wv"])), k, v, ai, pageTable, pos, start)
                out = _mm(ctx.transpose(0, 2, 1, 3).reshape(S, -1), lp["Wo"])
                ai += 1
            else:
                out, n = self._experts(lp, h, active, grouped=False)
                counts = counts + n
            x = x + out.astype(cd)
        left = jnp.sum(routing, axis=(0, 1)).astype(_I32)
        return (self._logits(params, x)[:, None], k, v, ssm, conv,
                jnp.zeros_like(routing), jnp.concatenate([counts, left]))
