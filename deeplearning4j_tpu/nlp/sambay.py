"""SambaY decoder-hybrid-decoder LM (Phi-4-mini-flash-reasoning,
arXiv:2507.06607) for the serving tier: Mamba, sliding-window and full
differential attention (arXiv:2410.05258), cross-attention onto ONE
shared KV layer, and Gated Memory Units.

Layers ``i = 0..L-1`` with ``half = L // 2``: Mamba at even ``i <= half``
(layer ``half`` also hands its scan output ``m = y`` to the GMUs), window
attention at odd ``i < half``, full attention at ``half + 1``, GMU at
even ``i > half``, cross-attention (queries only, onto the full layer's
keys and values) at odd ``i > half + 1``.  Every layer is pre-LayerNorm
mixer + pre-LayerNorm gated-SiLU FFN; no positional encoding anywhere;
tied input/output embedding.

What the model keeps between decode steps is THREE kinds of state, named
by :meth:`SambaYLM.cacheSpec` and held side by side by the scheduler's
``KVCachePool``:

- *paged* — the full layer's K/V rows, one per position, in pages that
  grow with the sequence; written by one layer, read by it and by every
  cross layer;
- *ring* — each window layer's last ``W`` K/V rows per slot.  Position
  ``p`` of a sequence whose first real token is ``start`` sits at ring
  row ``(p - start) % W``, so the live rows are always the interval ``0
  .. min(pos - start, W - 1)`` (attention without positions does not
  care in what order it meets them) and the step reads a ring as fixed
  pages of one slot, where it lies.  What is live follows from ``pos``
  and ``start`` alone: a reused slot's stale rows are masked, never
  zeroed;
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

from deeplearning4j_tpu.nn.conf.attention import (_CHUNK_ROWS, CacheSpec,
                                                  paged_attention_read)
from deeplearning4j_tpu.nlp.mamba import _mm, mamba_full, mamba_step
from deeplearning4j_tpu.nlp.served import ServedLM

__all__ = ["SambaYConfig", "SambaYLM"]

_F32 = jnp.float32
_I32 = jnp.int32
_NEG = -1e30


@dataclasses.dataclass
class SambaYConfig:
    vocabSize: int = 256
    nLayers: int = 8
    hiddenSize: int = 64
    nHeads: int = 8
    nKvHeads: int = 4
    ffnSize: int = 128
    window: int = 8
    mbPerLayer: int = 2
    stateSize: int = 4          # N
    convKernel: int = 4         # K
    expand: int = 2             # d_in = expand * hiddenSize
    dtRank: int = 4             # R
    eps: float = 1e-5
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
        half = self.nLayers // 2
        kinds = []
        for i in range(self.nLayers):
            if i % self.mbPerLayer == 0:
                kinds.append("mamba" if i <= half else "gmu")
            elif i < half:
                kinds.append("window")
            else:
                kinds.append("full" if i == half + 1 else "cross")
        return kinds


def _lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _ln(x, g, b, eps):
    x = x.astype(_F32)
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * g.astype(_F32) + b.astype(_F32)


class SambaYLM(ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns every kind of cache state, and
    the step form ``pagedLogits``, from which ``ServedLM`` builds the
    scheduler's fixed-shape decode step and admission write."""

    def __init__(self, config: Optional[SambaYConfig] = None, params=None,
                 **kw):
        self.config = config or SambaYConfig(**kw)
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype
        (3.85 B of them at the published sizes: a host draw would take
        minutes), one small program per kind of layer."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, ff, dIn = c.hiddenSize, c.ffnSize, c.innerSize
        N, K, R, dh = c.stateSize, c.convKernel, c.dtRank, c.headSize
        qd, kvd = c.nHeads * dh, c.nKvHeads * dh
        std = c.initializerRange

        @functools.partial(jax.jit, static_argnames=("kind",))
        def layer(key, kind):
            keys = iter(jax.random.split(key, 16))
            normal = lambda shape, s=std: (s * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            uniform = lambda shape, b: jax.random.uniform(
                next(keys), shape, _F32, -b, b).astype(dt)
            p = {"ln1_g": jnp.ones((d,), dt), "ln1_b": jnp.zeros((d,), dt),
                 "ln2_g": jnp.ones((d,), dt), "ln2_b": jnp.zeros((d,), dt),
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
                    D=jnp.ones((dIn,), dt), Wout=normal((dIn, d)))
            elif kind == "gmu":
                p.update(W1=normal((d, dIn)), W2=normal((dIn, d)))
            else:
                p.update(Wq=normal((d, qd)), Wo=normal((qd, d)),
                         sublnG=jnp.ones((2 * dh,), dt))
                for name in ("lq1", "lk1", "lq2", "lk2"):
                    p[name] = normal((dh,), 0.1)
                if kind != "cross":
                    p.update(Wk=normal((d, kvd)), Wv=normal((d, kvd)))
            return p

        @jax.jit
        def embedding(key):
            return (std * jax.random.normal(key, (c.vocabSize, d), _F32)
                    ).astype(dt)

        key = jax.random.PRNGKey(c.seed)
        return {"emb": embedding(jax.random.fold_in(key, 0)),
                "lnf_g": jnp.ones((d,), dt),
                "lnf_b": jnp.zeros((d,), dt),
                "layers": [layer(jax.random.fold_in(key, i + 1), kind)
                           for i, kind in enumerate(c.layerKinds())]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        pages for the ONE full layer, a ring of ``window`` rows a slot
        for every window layer, and the Mamba layers' recurrent state."""
        c = self.config
        kinds = c.layerKinds()
        nM = kinds.count("mamba")
        dt = jnp.dtype(c.dtype)
        return CacheSpec(
            pagedLayers=kinds.count("full"), kvHeads=c.nKvHeads,
            headSize=c.headSize, dtype=dt,
            ringLayers=kinds.count("window"), ringRows=c.window,
            slotState=(("ssm", (nM, c.stateSize, c.innerSize), _F32),
                       ("conv", (nM, c.convKernel - 1, c.innerSize), dt)))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _ffn(self, lp, x):
        u = _ln(x, lp["ln2_g"], lp["ln2_b"], self.config.eps)
        g = jax.nn.silu(_mm(u, lp["Wgate"])) * _mm(u, lp["Wup"])
        return x + _mm(g, lp["Wdown"]).astype(x.dtype)

    def _diff_queries(self, q):
        """``q (b, tq, H*dh)`` as ``(b, tq, G, 2R, 2*dh)``: a row is kept
        as ``G`` groups of ``2*dh`` channels — ``[k_g1 ; k_g2]``, a whole
        128-lane tile at the published head size — and each query is
        laid into its own half of that width (zeros in the other), so
        that K and V are contracted as they are stored and never split
        into 64-wide heads."""
        c = self.config
        b, tq, _ = q.shape
        G = c.nKvHeads // 2
        R = (c.nHeads // 2) // G
        q5 = q.reshape(b, tq, G, R, 2, 1, c.headSize)
        eye = jnp.eye(2, dtype=q.dtype)[:, :, None]
        return (q5 * eye).reshape(b, tq, G, R * 2, 2 * c.headSize)

    @staticmethod
    def _diff_lambda(lp, i):
        f = lambda n: lp[n].astype(_F32)
        return jnp.exp(jnp.sum(f("lq1") * f("lk1"))) \
            - jnp.exp(jnp.sum(f("lq2") * f("lk2"))) + _lambda_init(i)

    @staticmethod
    def _sub_norm(lp, i, o):
        """The differential context's RMS norm over a group's ``2*dh``
        channels, ``o (b, tq, G, R, 2*dh)`` float32 -> ``(b, tq, H*dh)``."""
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + 1e-5) * lp["sublnG"].astype(_F32) \
            * (1.0 - _lambda_init(i))
        return o.reshape(o.shape[:2] + (-1,))

    def _diff_attend(self, lp, i, q, kRows, vRows, valid):
        """Differential attention of ``q (b, tq, H*dh)`` against rows
        ``kRows, vRows (b, T, KV*dh)`` with ``valid (b, tq, T)``: every
        attention layer's in the full-sequence forms (the step reads
        pages and rings where they lie: :meth:`_diff_attend_paged`).

        Query heads pair up (20 pairs) and KV heads pair up (10 pairs);
        query pair ``p`` reads KV pair ``g = p // R``, laid out as
        :meth:`_diff_queries` says."""
        c = self.config
        b, tq, _ = q.shape
        T = kRows.shape[1]
        dh = c.headSize
        G = c.nKvHeads // 2
        cd = kRows.dtype
        qe = self._diff_queries(q).astype(cd)
        k4 = kRows.reshape(b, T, G, 2 * dh)
        v4 = vRows.reshape(b, T, G, 2 * dh)
        s = jnp.einsum("bqgac,btgc->bgqat", qe, k4,
                       preferred_element_type=_F32) * (1.0 / math.sqrt(dh))
        s = jnp.where(valid[:, None, :, None, :], s, _NEG)
        a = jax.nn.softmax(s, axis=-1).reshape(b, G, tq, -1, 2, T)
        a = a[..., 0, :] - self._diff_lambda(lp, i) * a[..., 1, :]
        o = jnp.einsum("bgqrt,btgc->bqgrc", a.astype(cd), v4,
                       preferred_element_type=_F32)
        return self._sub_norm(lp, i, o)

    def _diff_attend_paged(self, lp, i, q, k, v, li, pageTable, pos, start):
        """:meth:`_diff_attend` of ``q (S, tq, H*dh)`` against the rows
        ``start <= j <= pos`` of layer ``li`` of the pools ``k, v
        (layers, pages, pageSize, KV*dh)`` where they lie, through
        :func:`paged_attention_read` — the paged layer's pages, or a
        window layer's ring as its slot's fixed pages
        (:meth:`_ring_step`): in :meth:`_diff_queries`' layout it
        is plain grouped attention, ``H`` query heads of ``2*dh`` lanes
        on ``G`` KV heads of ``2*dh``, scores scaled by ``1/sqrt(dh)``,
        the context over a group's whole ``2*dh`` lanes of V.  The
        context is linear in the softmax weights, so the pair's
        difference ``a1 - lambda a2`` is taken of the two contexts, in
        float32, and no weight is rounded on its way to V."""
        c = self.config
        S, tq, _ = q.shape
        dh = c.headSize
        G = c.nKvHeads // 2
        qe = self._diff_queries(q.astype(_F32)).reshape(
            S, tq, c.nHeads, 2 * dh).transpose(0, 2, 1, 3)
        ctx = paged_attention_read(qe, k, v, li, pageTable, pos, start,
                                   scale=dh ** -0.5)       # (S, H, tq, 2dh)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(S, tq, G, -1, 2, 2 * dh)
        return self._sub_norm(
            lp, i, ctx[..., 0, :] - self._diff_lambda(lp, i) * ctx[..., 1, :])

    def _ring_step(self, lp, i, q, kNew, vNew, ringK, ringV, wi, pos, start):
        """A window layer's decode step over its ring, layer ``wi`` of
        the stacks ``ringK, ringV (layers, S, W, KV*dh)``: this step's
        row ``kNew, vNew (S, KV*dh)`` goes to ring row ``(pos - start) %
        W`` (a slot whose ``pos`` is 0 keeps its rows as they are), then
        ``q (S, 1, H*dh)`` attends over the live rows ``0 .. min(pos -
        start, W - 1)`` where they lie (:meth:`_diff_attend_paged`).
        ``(context, ringK, ringV)``.

        What makes a ring a paged pool, with nothing copied: the stack
        viewed as ``(layers, S * W/R, R, KV*dh)`` is a reshape of its
        leading dimensions, and its page table is a constant — slot ``s``
        owns the ``P = W/R`` pages ``s * P .. (s + 1) * P - 1`` for good.
        A page is ``R`` rows: what the read's kernel copies as ONE chunk
        (``_CHUNK_ROWS``), the whole ring where that is shorter."""
        L, S, W, w = ringK.shape
        R = math.gcd(W, _CHUNK_ROWS)
        P = W // R
        slots = jnp.arange(S, dtype=_I32)
        age = jnp.maximum(pos - start, 0)
        row = age % W
        put = lambda ring, new: ring.at[wi, slots, row].set(jnp.where(
            (pos > 0)[:, None], new, ring[wi, slots, row]))
        ringK, ringV = put(ringK, kNew), put(ringV, vNew)
        pages = lambda ring: ring.reshape(L, S * P, R, w)
        last = jnp.minimum(age, W - 1)
        o = self._diff_attend_paged(
            lp, i, q, pages(ringK), pages(ringV), wi,
            jnp.arange(S * P, dtype=_I32).reshape(S, P), last,
            jnp.zeros_like(last))
        return o, ringK, ringV

    def _logits(self, params, x):
        h = _ln(x, params["lnf_g"], params["lnf_b"], self.config.eps)
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
        decode would continue from: the full layer's K/V rows, every
        window layer's K/V rows, every Mamba layer's final state and
        last ``K - 1`` convolution inputs.  A pad position advances
        nothing: its ``u`` and its ``Δ`` are zero, and no key is valid
        there."""
        c = self.config
        T = tokens.shape[1]
        K, W = c.convKernel, c.window
        half = c.nLayers // 2
        kpos = jnp.arange(T, dtype=_I32)
        real = (kpos[None, :] >= start[:, None])             # (b, T)
        realF = real.astype(_F32)[..., None]
        causal = kpos[None, :, None] >= kpos[None, None, :]
        valid = causal & real[:, None, :]                    # (b, T, T)
        inWin = kpos[None, :, None] - kpos[None, None, :] < W
        x = params["emb"][tokens]
        cd = x.dtype
        mem = kvRows = None
        pagedK, pagedV, ringK, ringV, ssm, conv = [], [], [], [], [], []
        for i, (kind, lp) in enumerate(zip(c.layerKinds(),
                                           params["layers"])):
            h = _ln(x, lp["ln1_g"], lp["ln1_b"], c.eps)
            if kind == "mamba":
                out, y, s, tail = mamba_full(
                    lp, h, realF, N=c.stateSize, K=K, R=c.dtRank)
                ssm.append(s)
                conv.append(tail.astype(cd))
                if i == half:
                    mem = y
            elif kind == "gmu":
                out = _mm(jax.nn.silu(_mm(h, lp["W1"])) * mem, lp["W2"])
            else:
                q = _mm(h, lp["Wq"]).astype(cd)
                if kind == "cross":
                    kR, vR = kvRows
                else:
                    kR = _mm(h, lp["Wk"]).astype(cd)
                    vR = _mm(h, lp["Wv"]).astype(cd)
                if kind == "full":
                    kvRows = (kR, vR)
                    pagedK.append(kR)
                    pagedV.append(vR)
                elif kind == "window":
                    ringK.append(self._ring_rows(kR, start))
                    ringV.append(self._ring_rows(vR, start))
                o = self._diff_attend(
                    lp, i, q, kR, vR,
                    valid & inWin if kind == "window" else valid)
                out = _mm(o, lp["Wo"])
            x = self._ffn(lp, x + out.astype(cd))
        # the paged stacks in paged_rows_write's form (L, b, h, T, d):
        # one "head" as wide as a row
        state = (jnp.stack(pagedK)[:, :, None], jnp.stack(pagedV)[:, :, None],
                 jnp.stack(ringK), jnp.stack(ringV), jnp.stack(ssm),
                 jnp.stack(conv))
        return x, state

    def _ring_rows(self, rows, start):
        """The last ``min(T, W)`` real rows of ``rows (b, T, w)``, whose
        first real row is ``start (b,)``, in ring order: position ``p``
        sits at ring row ``(p - start) % W``, so ring row ``r`` takes
        the newest position that is ``r`` more than ``start`` modulo
        ``W`` (a row no position has reached yet takes whatever is at
        hand: the step writes it before it reads it)."""
        W = self.config.window
        T = rows.shape[1]
        r = jnp.arange(min(T, W), dtype=_I32)[None, :]
        p = (T - 1) - (T - 1 - start[:, None] - r) % W
        return jnp.take_along_axis(rows, jnp.maximum(p, 0)[:, :, None],
                                   axis=1)

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
        """``(last logits (b, vocab), kStack, vStack, ringK, ringV, ssm,
        conv)``: the paged stacks in :func:`paged_rows_write`'s form ``(1,
        b, 1, t, KV*dh)`` and the slot state ``(layers, b, ...)`` in the
        pool's order."""
        def run(params, tokens, start):
            x, state = self._run_full(params, tokens, start)
            return (self._logits(params, x[:, -1]),) + state
        return jax.jit(run)

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, k, v, ringK, ringV, ssm, conv, toks,
                    pageTable, pos, start):
        """One token per slot (``toks (S, 1)``) against the pool's
        arrays: ``((S, 1, vocab) logits, k, v, ringK, ringV, ssm,
        conv)``.  The full layer writes its row into its page and it,
        and the cross layers after it, read the pages through
        :func:`paged_attention_read` (lowered for one TPU: the kernel
        over the slots' live pages; elsewhere the gathered reference);
        a window layer writes its row into its ring and reads the ring's
        live rows the same way, as its slot's fixed pages
        (:meth:`_ring_step`): one read for paged rows and ring rows.
        A slot whose ``pos`` is 0 holds no sequence (or is
        deferred a round): its paged write lands on the scratch page
        through its zeroed page table, and its ring rows and recurrent
        state are left as they are."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "a recurrent state advances one token a step: speculative "
                "verification (tq > 1) would need its roll-back")
        half = c.nLayers // 2
        ps = k.shape[2]
        rows = jnp.arange(S, dtype=_I32)
        active = pos > 0
        # the paged layer: where this step's row goes
        phys = pageTable[rows, pos // ps]
        off = pos % ps
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        keep = lambda new, old: jnp.where(
            active.reshape((S,) + (1,) * (new.ndim - 1)), new, old)
        mem = None
        mi = wi = 0
        for i, (kind, lp) in enumerate(zip(c.layerKinds(),
                                           params["layers"])):
            h = _ln(x, lp["ln1_g"], lp["ln1_b"], c.eps)
            if kind == "mamba":
                out, y, s, win = mamba_step(
                    lp, h, ssm[mi], conv[mi], keep, N=c.stateSize,
                    R=c.dtRank)
                conv = conv.at[mi].set(win)
                ssm = ssm.at[mi].set(s)
                if i == half:
                    mem = y
                mi += 1
            elif kind == "gmu":
                out = _mm(jax.nn.silu(_mm(h, lp["W1"])) * mem, lp["W2"])
            else:
                q = _mm(h, lp["Wq"]).astype(cd)[:, None]      # (S, 1, H*dh)
                if kind != "cross":
                    kN = _mm(h, lp["Wk"]).astype(cd)
                    vN = _mm(h, lp["Wv"]).astype(cd)
                if kind == "window":
                    o, ringK, ringV = self._ring_step(
                        lp, i, q, kN, vN, ringK, ringV, wi, pos, start)
                    wi += 1
                else:
                    if kind == "full":
                        k = k.at[0, phys, off].set(kN.astype(k.dtype))
                        v = v.at[0, phys, off].set(vN.astype(v.dtype))
                    o = self._diff_attend_paged(lp, i, q, k, v, 0, pageTable,
                                                pos, start)
                out = _mm(o[:, 0], lp["Wo"])
            x = self._ffn(lp, x + out.astype(cd))
        return (self._logits(params, x)[:, None], k, v, ringK, ringV, ssm,
                conv)
