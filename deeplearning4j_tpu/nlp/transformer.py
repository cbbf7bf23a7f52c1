"""Decoder-only transformer LM with incremental (KV-cached) decode.

The training side of this repo already runs transformer encoders (the
SameDiff BERT of ``zoo/bert.py``, flash attention for long context);
serving generative traffic needs the *decode* discipline those graphs
don't have: generation re-run through a full forward is O(t) per token and
re-traces on every prompt length.  This model keeps decode O(1) per token
by carrying a :class:`~deeplearning4j_tpu.nn.conf.attention.KVCache`
through every attention layer, with all executable shapes STATIC:

- :meth:`prefill` runs the prompt through the stack once (causal
  attention dispatching through ``parallel.ring.dot_product_attention``,
  i.e. the flash kernel on TPU for long prompts) and fills the caches;
- :meth:`decodeStep` feeds ONE token per example against the caches —
  fixed (batch, capacity) shapes, so the serving tier warms exactly one
  executable per batch bucket and never re-traces in steady state;
- left-padding support (``lengths``) keeps ragged prompts bucketable:
  every example ends at the same position, so the cache write position
  stays one scalar (see ``KVCache.start``).

Weights follow the pre-LN GPT block (LN → attention → residual, LN → FFN
→ residual) with tied input/output embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.attention import (CacheSpec, KVCache,
                                                  cached_attention,
                                                  paged_attention,
                                                  paged_prefill_write,
                                                  paged_step_tokens)

__all__ = ["TransformerLMConfig", "TransformerLM"]


@dataclasses.dataclass
class TransformerLMConfig:
    vocabSize: int = 256
    nLayers: int = 2
    nHeads: int = 4
    headSize: int = 16
    ffnMult: int = 4
    maxLen: int = 128          # cache capacity == max prompt + generation
    initializerRange: float = 0.02
    seed: int = 0

    @property
    def hiddenSize(self) -> int:
        return self.nHeads * self.headSize


class TransformerLM:
    """GPT-style causal LM; ``generate`` == prefill + N decode steps."""

    def __init__(self, config: Optional[TransformerLMConfig] = None, **kw):
        self.config = config or TransformerLMConfig(**kw)
        self.params = self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        c = self.config
        rng = np.random.RandomState(c.seed)
        H, F = c.hiddenSize, c.ffnMult * c.hiddenSize

        def init(*shape):
            return jnp.asarray(
                (rng.randn(*shape) * c.initializerRange).astype(np.float32))

        # float32 spelled out on every leaf: the package enables x64, so
        # a dtype-less jnp.ones/zeros is float64 and promotes everything
        # after the first LayerNorm (a TPU has no f64 unit)
        f32 = jnp.float32
        p = {"emb": init(c.vocabSize, H), "pos": init(c.maxLen, H),
             "lnf_g": jnp.ones((H,), f32), "lnf_b": jnp.zeros((H,), f32),
             "layers": []}
        for _ in range(c.nLayers):
            p["layers"].append({
                "ln1_g": jnp.ones((H,), f32), "ln1_b": jnp.zeros((H,), f32),
                "Wq": init(H, H), "Wk": init(H, H), "Wv": init(H, H),
                "Wo": init(H, H),
                "ln2_g": jnp.ones((H,), f32), "ln2_b": jnp.zeros((H,), f32),
                "Wi": init(H, F), "bi": jnp.zeros((F,), f32),
                "Wp": init(F, H), "bp": jnp.zeros((H,), f32)})
        return p

    # ------------------------------------------------------------------
    @staticmethod
    def _ln(x, g, b):
        # variance spelled out: jnp.var lowers with a scalar f64 NaN
        # constant under x64, and the serving executables carry no f64
        xc = x - jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + 1e-5) * g + b

    def _heads(self, y):
        b, t, _ = y.shape
        c = self.config
        return y.reshape(b, t, c.nHeads, c.headSize).transpose(0, 2, 1, 3)

    def _merge(self, ctx):
        b, _, t, _ = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(b, t, -1)

    def _block_full(self, lp, x, mask):
        """Full-sequence causal block (prefill/training).  Dispatches the
        score chain through ``dot_product_attention`` — flash on TPU for
        long unmasked prompts, mask-honoring dense/blockwise otherwise."""
        from deeplearning4j_tpu.parallel.ring import dot_product_attention
        h = self._ln(x, lp["ln1_g"], lp["ln1_b"])
        qh = self._heads(jnp.matmul(h, lp["Wq"]))
        kh = self._heads(jnp.matmul(h, lp["Wk"]))
        vh = self._heads(jnp.matmul(h, lp["Wv"]))
        ctx = dot_product_attention(qh, kh, vh, mask=mask, causal=True)
        x = x + jnp.matmul(self._merge(ctx), lp["Wo"])
        h = self._ln(x, lp["ln2_g"], lp["ln2_b"])
        ff = jax.nn.gelu(jnp.matmul(h, lp["Wi"]) + lp["bi"])
        return x + jnp.matmul(ff, lp["Wp"]) + lp["bp"], (kh, vh)

    def _block_cached(self, lp, x, cache: KVCache):
        h = self._ln(x, lp["ln1_g"], lp["ln1_b"])
        qh = self._heads(jnp.matmul(h, lp["Wq"]))
        kh = self._heads(jnp.matmul(h, lp["Wk"]))
        vh = self._heads(jnp.matmul(h, lp["Wv"]))
        ctx, cache = cached_attention(qh, kh, vh, cache)
        x = x + jnp.matmul(self._merge(ctx), lp["Wo"])
        h = self._ln(x, lp["ln2_g"], lp["ln2_b"])
        ff = jax.nn.gelu(jnp.matmul(h, lp["Wi"]) + lp["bi"])
        return x + jnp.matmul(ff, lp["Wp"]) + lp["bp"], cache

    def _embed(self, params, tokens, pos_ids):
        x = params["emb"][tokens]                      # (b, t, H)
        return x + params["pos"][pos_ids]

    def _logits(self, params, x):
        h = self._ln(x, params["lnf_g"], params["lnf_b"])
        return jnp.matmul(h, params["emb"].T)          # tied head

    # ------------------------------------------------------------------
    # full forward (the recompute baseline the KV path must match)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _fwd(self):
        def run(params, tokens):
            t = tokens.shape[1]
            x = self._embed(params, tokens,
                            jnp.arange(t, dtype=jnp.int32)[None, :])
            for lp in params["layers"]:
                x, _ = self._block_full(lp, x, None)
            return self._logits(params, x)
        return jax.jit(run)

    def forward(self, tokens) -> jax.Array:
        """Full causal forward: (b, t) int32 -> (b, t, vocab) logits."""
        return self._fwd(self.params, jnp.asarray(tokens, jnp.int32))

    # ------------------------------------------------------------------
    # incremental decode
    # ------------------------------------------------------------------
    def initCaches(self, batch: int) -> List[KVCache]:
        c = self.config
        return [KVCache.create(batch, c.nHeads, c.maxLen, c.headSize)
                for _ in range(c.nLayers)]

    @functools.cached_property
    def _prefillFn(self):
        def run(params, tokens, start, padded):
            # start[b] = index of the first REAL token (left padding);
            # position ids count from the real start so padded and
            # unpadded prompts see identical positional embeddings.
            # ``padded`` is static: unpadded prompts keep mask=None so the
            # causal dispatch stays flash-eligible on TPU for long context
            b, t = tokens.shape
            kpos = jnp.arange(t, dtype=jnp.int32)[None, :]
            pos_ids = jnp.maximum(kpos - start[:, None], 0)
            mask = (kpos >= start[:, None]).astype(jnp.float32) \
                if padded else None                              # (b, t)
            x = self._embed(params, tokens, pos_ids)
            caches = []
            for lp in params["layers"]:
                x, (kh, vh) = self._block_full(lp, x, mask)
                cache = KVCache.create(b, self.config.nHeads,
                                       self.config.maxLen,
                                       self.config.headSize,
                                       kh.dtype, start=start)
                k = jax.lax.dynamic_update_slice(cache.k, kh, (0, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(cache.v, vh, (0, 0, 0, 0))
                caches.append(KVCache(k, v, jnp.asarray(t, jnp.int32),
                                      start))
            return self._logits(params, x[:, -1:])[:, 0], caches
        return jax.jit(run, static_argnames=("padded",))

    def prefill(self, tokens, lengths=None):
        """Run the prompt once, filling every layer's cache.

        ``tokens`` (b, t) int32, LEFT-padded when ragged; ``lengths`` (b,)
        gives each example's real token count (defaults to full t).
        Returns ``(last_logits (b, vocab), caches)`` — the logits predict
        the first generated token.
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        t = tokens.shape[1]
        if t > self.config.maxLen:
            raise ValueError(f"prompt length {t} exceeds cache capacity "
                             f"{self.config.maxLen}")
        if lengths is None:
            start = jnp.zeros((tokens.shape[0],), jnp.int32)
        else:
            start = t - jnp.asarray(lengths, jnp.int32)
        return self._prefillFn(self.params, tokens, start,
                               lengths is not None)

    def _decode_math(self, params, tok, caches):
        """One incremental step against dense caches: tok (b,) ->
        ((b, vocab) logits, new caches).  The shared body of
        ``_decodeFn`` and the draft-proposal scan."""
        pos_ids = (caches[0].pos - caches[0].start)[:, None]  # (b, 1)
        x = self._embed(params, tok[:, None], pos_ids)
        new = []
        for lp, cache in zip(params["layers"], caches):
            x, cache = self._block_cached(lp, x, cache)
            new.append(cache)
        return self._logits(params, x)[:, 0], new

    @functools.cached_property
    def _decodeFn(self):
        def run(params, tok, caches):
            # tok: (b,) int32 — ONE new token per example
            return self._decode_math(params, tok, caches)
        return jax.jit(run)

    def decodeStep(self, tok, caches):
        """One generated token per example: (b,) int32 + caches ->
        ((b, vocab) logits, new caches).  O(capacity) per call — the
        prefix never re-enters the layer stack."""
        return self._decodeFn(self.params, jnp.asarray(tok, jnp.int32),
                              caches)

    # ------------------------------------------------------------------
    # speculative decode: draft proposes, target verifies in ONE forward
    # ------------------------------------------------------------------
    @functools.cached_property
    def _verifyFn(self):
        """Verify ``k`` proposed tokens in ONE batched forward: feeds all
        k against the caches (``cached_attention`` handles tq > 1) and
        returns the target's greedy token AFTER each prefix — the
        accept-prefix comparison happens on the host."""
        def run(params, toks, caches):
            b, k = toks.shape
            pos_ids = jnp.maximum(
                (caches[0].pos - caches[0].start)[:, None] +
                jnp.arange(k, dtype=jnp.int32)[None, :], 0)
            x = self._embed(params, toks, pos_ids)
            new = []
            for lp, cache in zip(params["layers"], caches):
                x, cache = self._block_cached(lp, x, cache)
                new.append(cache)
            greedy = jnp.argmax(self._logits(params, x),
                                axis=-1).astype(jnp.int32)
            return greedy, new
        return jax.jit(run)

    def verifySteps(self, toks, caches):
        """Target-side verification: toks (b, k) int32 (the last emitted
        token followed by the draft's proposals) -> ((b, k) greedy
        tokens, caches advanced k).  Greedy token j is the target's
        prediction after prefix ``toks[:, :j+1]`` — identical math to j
        sequential :meth:`decodeStep` calls, ONE dispatch.  On a partial
        accept the caller rolls back by rebuilding the caches with a
        smaller ``pos`` (stale K/V past ``pos`` are overwritten before
        they can ever be attended)."""
        return self._verifyFn(self.params, jnp.asarray(toks, jnp.int32),
                              caches)

    def _proposeFn(self, k: int):
        """Jitted draft proposal: ``k`` greedy tokens in ONE dispatch
        (the per-token loop is a ``lax.scan`` INSIDE the executable, so
        a cheap draft model is not billed k dispatch round-trips).  The
        scan runs k+1 steps so the cache also holds K/V for the k-th
        proposal — a full accept then needs no cache repair."""
        fns = self.__dict__.setdefault("_proposeFns", {})
        if k not in fns:
            def run(params, tok, caches):
                def body(carry, _):
                    tok, caches = carry
                    logits, caches = self._decode_math(params, tok, caches)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (nxt, caches), nxt
                (_, caches), props = jax.lax.scan(
                    body, (tok, caches), None, length=k + 1)
                return jnp.transpose(props)[:, :k], caches
            fns[k] = jax.jit(run)
        return fns[k]

    def proposeK(self, tok, caches, k: int):
        """Draft entry point: (b,) last tokens -> ((b, k) proposals,
        caches advanced k+1)."""
        return self._proposeFn(int(k))(
            self.params, jnp.asarray(tok, jnp.int32), caches)

    def speculative_generate(self, draft: "TransformerLM", prompts,
                             maxNewTokens: int, draftK: int = 4,
                             lengths=None, returnStats: bool = False):
        """Greedy decode accelerated by a small draft model — output is
        BIT-IDENTICAL to :meth:`generate` (accept-prefix rule: every
        emitted token is the target's own greedy argmax; the draft only
        decides how many of them one verification dispatch yields).

        Per round: the draft proposes ``draftK`` tokens in one fused
        scan, the target verifies all of them in ONE batched forward,
        and the longest matching prefix plus the target's first
        correction are emitted — between 1 and ``draftK + 1`` tokens for
        two dispatches, vs one token per dispatch for plain decode.

        Serves ONE sequence per call (per-example accept lengths
        diverge under batching; the continuous-batching scheduler's
        per-slot page tables handle that case).  Requires
        ``t + maxNewTokens + draftK <= maxLen``: a rejected round still
        wrote its speculative K/V before the roll-back, so the cache
        needs the extra headroom.
        """
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None, :]
        if prompts.shape[0] != 1:
            raise ValueError(
                "speculative_generate serves one sequence at a time "
                "(per-example accept lengths diverge; use the "
                "continuous-batching scheduler for batched speculation)")
        draftK = int(draftK)
        if draftK < 1:
            raise ValueError("draftK must be >= 1")
        if draft.config.vocabSize != self.config.vocabSize:
            raise ValueError("draft and target must share a vocabulary")
        t = prompts.shape[1]
        if t + maxNewTokens + draftK > self.config.maxLen:
            raise ValueError(
                f"prompt {t} + maxNewTokens {maxNewTokens} + draftK "
                f"{draftK} exceeds cache capacity {self.config.maxLen} "
                "(speculative rounds write draftK tokens of K/V ahead)")
        if t + maxNewTokens + draftK > draft.config.maxLen:
            raise ValueError(
                f"draft cache capacity {draft.config.maxLen} cannot hold "
                f"prompt {t} + maxNewTokens {maxNewTokens} + draftK "
                f"{draftK}")
        logits, caches = self.prefill(prompts, lengths)
        _, dcaches = draft.prefill(prompts, lengths)
        # jaxlint: sync-ok -- the accept-prefix rule is a host decision: one small D2H per round by design
        tok = int(np.argmax(np.asarray(logits[0])))
        emitted = [tok]
        proposed = accepted = rounds = 0
        while len(emitted) < maxNewTokens:
            # pre-propose/pre-verify write indices: the roll-back below
            # rebuilds both cache sets relative to THESE (reading pos
            # after the dispatch would bake the speculative advance in)
            pos0 = caches[0].pos
            dpos0 = dcaches[0].pos
            props, dcaches = draft.proposeK(
                np.asarray([tok], np.int32), dcaches, draftK)
            # jaxlint: sync-ok -- proposals feed the verify batch through host concat (accept rule is host-side)
            props = np.asarray(props)[0]                     # (draftK,)
            verifyIn = np.concatenate(
                [np.asarray([tok], np.int32), props])[None, :]
            greedy, caches = self.verifySteps(verifyIn, caches)
            # jaxlint: sync-ok -- greedy tokens ARE the output; comparison against proposals is host-side
            greedy = np.asarray(greedy)[0]                   # (draftK+1,)
            a = 0
            while a < draftK and props[a] == greedy[a]:
                a += 1
            emitted.extend(int(g) for g in greedy[:a + 1])
            tok = int(greedy[a])
            proposed += draftK
            accepted += a
            rounds += 1
            # roll back: only the accepted prefix (plus the verified
            # input token) is real — stale K/V past pos are overwritten
            # before any later query can attend to them
            newPos = pos0 + a + 1
            caches = [KVCache(c.k, c.v, newPos, c.start) for c in caches]
            dcaches = [KVCache(c.k, c.v, dpos0 + a + 1, c.start)
                       for c in dcaches]
        out = np.asarray(emitted[:maxNewTokens], np.int32)[None, :]
        if returnStats:
            return out, {"proposed": proposed, "accepted": accepted,
                         "rounds": rounds,
                         "acceptRate": accepted / proposed if proposed
                         else 0.0}
        return out

    # ------------------------------------------------------------------
    # paged decode — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    @functools.cached_property
    def _prefillRawFn(self):
        """Prefill that returns the per-layer K/V heads STACKED
        ((nLayers, b, h, t, d)) instead of materializing full-capacity
        dense caches — the continuous scheduler copies them straight
        into pool pages."""
        def run(params, tokens, start):
            b, t = tokens.shape
            kpos = jnp.arange(t, dtype=jnp.int32)[None, :]
            pos_ids = jnp.maximum(kpos - start[:, None], 0)
            mask = (kpos >= start[:, None]).astype(jnp.float32)
            x = self._embed(params, tokens, pos_ids)
            ks, vs = [], []
            for lp in params["layers"]:
                x, (kh, vh) = self._block_full(lp, x, mask)
                ks.append(kh)
                vs.append(vh)
            return (self._logits(params, x[:, -1:])[:, 0],
                    jnp.stack(ks), jnp.stack(vs))
        return jax.jit(run)

    def prefillRaw(self, tokens, lengths=None):
        """(b, t) LEFT-padded prompt -> (last logits (b, vocab),
        kStack, vStack (nLayers, b, h, t, d)).  Always mask-padded (one
        executable per prompt bucket regardless of raggedness)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        t = tokens.shape[1]
        if t > self.config.maxLen:
            raise ValueError(f"prompt length {t} exceeds cache capacity "
                             f"{self.config.maxLen}")
        if lengths is None:
            start = jnp.zeros((tokens.shape[0],), jnp.int32)
        else:
            start = t - jnp.asarray(lengths, jnp.int32)
        return self._prefillRawFn(self.params, tokens, start)

    def restartFromPrompt(self, tokens, lengths=None):
        """Restart hook for preemption and serving failover: rebuild a
        sequence's KV state from its ORIGINAL prompt, with exactly the
        dispatch the first admission used (same executable, same bucket
        shape), so the step-by-step replay that follows regenerates the
        identical token prefix — greedy decode is deterministic given
        identical ops on identical shapes.  The continuous batcher
        additionally teacher-forces the already-delivered tokens during
        replay, so the prefix a client sees never depends on bit-wise
        reproducibility across replicas (a quantized or differently
        placed survivor can override this hook and still satisfy the
        exactly-once contract)."""
        return self.prefillRaw(tokens, lengths=lengths)

    def cacheSpec(self) -> CacheSpec:
        """What the layers keep between decode steps, for the
        scheduler's pool: every layer owns K/V pages, nothing else."""
        c = self.config
        return CacheSpec(c.nLayers, c.nHeads, c.headSize)

    def _paged_block(self, lp, li, x, poolK, poolV, pageTable, pos, start):
        """Transformer block ``li`` against the stacked paged pools (the
        ``_block_cached`` math with :func:`paged_attention` in place of
        the private dense cache)."""
        h = self._ln(x, lp["ln1_g"], lp["ln1_b"])
        qh = self._heads(jnp.matmul(h, lp["Wq"]))
        kh = self._heads(jnp.matmul(h, lp["Wk"]))
        vh = self._heads(jnp.matmul(h, lp["Wv"]))
        ctx, poolK, poolV = paged_attention(qh, kh, vh, poolK, poolV, li,
                                            pageTable, pos, start)
        x = x + jnp.matmul(self._merge(ctx), lp["Wo"])
        h = self._ln(x, lp["ln2_g"], lp["ln2_b"])
        ff = jax.nn.gelu(jnp.matmul(h, lp["Wi"]) + lp["bi"])
        return x + jnp.matmul(ff, lp["Wp"]) + lp["bp"], poolK, poolV

    def pagedLogits(self, params, poolK, poolV, toks, pageTable, pos,
                    start):
        """toks (S, tq) against the stacked pools (L, pages, pageSize,
        nHeads*headSize): returns ((S, tq, vocab) logits, pools) — what
        the paged decode step takes its arg-max of, and what a parity
        check compares with :meth:`forward`.  Every layer writes and
        reads the stacked pools in place.  Position-embedding ids are
        clipped so a speculative over-write past ``maxLen`` (tokens that
        will be discarded by the accept rule) can't index out of the
        table."""
        tq = toks.shape[1]
        pos_ids = jnp.clip(
            (pos - start)[:, None] + jnp.arange(tq, dtype=jnp.int32),
            0, self.config.maxLen - 1)
        x = params["emb"][toks] + params["pos"][pos_ids]
        for li, lp in enumerate(params["layers"]):
            x, poolK, poolV = self._paged_block(lp, li, x, poolK, poolV,
                                                pageTable, pos, start)
        return self._logits(params, x), poolK, poolV

    def _paged_step_math(self, params, poolK, poolV, toks, pageTable,
                         pos, start):
        """:meth:`pagedLogits` reduced to (S, tq) greedy tokens."""
        logits, poolK, poolV = self.pagedLogits(params, poolK, poolV, toks,
                                                pageTable, pos, start)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), poolK, poolV

    def buildPagedDecodeFn(self):
        """FRESH jitted paged decode/verify step over a
        ``KVCachePool``'s buffers: ``(params, poolK, poolV, toks (S,tq),
        prev (S,1), pageTable, pos, start) -> (greedy (S,tq), poolK,
        poolV)``.  tq=1 is the plain decode step; tq=draftK+1 the
        speculative verify.  A slot whose ``toks`` is -1 takes ``prev``,
        the step before's greedy output, still on the device
        (:func:`paged_step_tokens`).  Pool buffers are DONATED (the pool
        swaps in the returned arrays).  A fresh function identity per
        build is deliberate: JAX's jaxpr cache keys on function identity
        + avals, so reusing one closure across a pool/plan rebuild could
        resurrect constraints traced for the old layout — the scheduler
        pops and rebuilds these on every pool/plan change."""
        def step(params, poolK, poolV, toks, prev, pageTable, pos, start):
            return self._paged_step_math(
                params, poolK, poolV, paged_step_tokens(toks, prev),
                pageTable, pos, start)
        return jax.jit(step, donate_argnums=(1, 2))

    def buildPagedProposeFn(self, draftK: int):
        """FRESH jitted paged draft proposal: k greedy tokens per slot in
        ONE dispatch (``lax.scan`` inside the executable; k+1 steps so
        the k-th proposal's K/V is already paged in on a full accept).
        Same donation and fresh-identity contract as
        :meth:`buildPagedDecodeFn`."""
        draftK = int(draftK)

        def propose(params, poolK, poolV, tok, pageTable, pos, start):
            def body(carry, _):
                poolK, poolV, tok, pos = carry
                greedy, poolK, poolV = self._paged_step_math(
                    params, poolK, poolV, tok[:, None], pageTable, pos,
                    start)
                nxt = greedy[:, 0]
                return (poolK, poolV, nxt, pos + 1), nxt
            (poolK, poolV, _, _), props = jax.lax.scan(
                body, (poolK, poolV, tok, pos), None, length=draftK + 1)
            return jnp.transpose(props)[:, :draftK], poolK, poolV
        return jax.jit(propose, donate_argnums=(1, 2))

    def buildPagedPrefillWriteFn(self):
        """FRESH jitted pool write: copy one sequence's stacked prefill
        K/V ((L, h, Tp, d), Tp a page multiple) into the pages named by
        ``pageIds`` ((Tp/pageSize,) int32).  One cache entry per prompt
        bucket (warmed at start).  The layout is
        :func:`paged_prefill_write`'s; the wrapper gives each build its
        own identity and the program the name traces know it by
        (``jit_write``).  The scheduler also passes the ``slot``; pages
        are all this model keeps, so it goes unused."""
        def write(poolK, poolV, kStack, vStack, pageIds, slot=None):
            return paged_prefill_write(poolK, poolV, kStack, vStack,
                                       pageIds)
        return jax.jit(write, donate_argnums=(0, 1))

    def compileCacheSize(self) -> int:
        """Total jit-cache entries across the forward/prefill/decode/
        verify/propose executables — the serving tier's compile hit/miss
        probe."""
        n = 0
        fns = [self.__dict__.get(name)
               for name in ("_fwd", "_prefillFn", "_decodeFn",
                            "_verifyFn", "_prefillRawFn")]
        fns.extend(self.__dict__.get("_proposeFns", {}).values())
        for fn in fns:
            if fn is not None:
                try:
                    n += int(fn._cache_size())
                except Exception:
                    pass
        return n

    # ------------------------------------------------------------------
    def generate(self, prompts, maxNewTokens: int, lengths=None
                 ) -> np.ndarray:
        """Greedy decode: (b, t) prompts -> (b, maxNewTokens) int32.

        Capacity check: t + maxNewTokens must fit ``maxLen`` (the caches
        are fixed-size by design — growing them would re-trace)."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None, :]
        t = prompts.shape[1]
        if t + maxNewTokens > self.config.maxLen:
            raise ValueError(
                f"prompt {t} + maxNewTokens {maxNewTokens} exceeds cache "
                f"capacity {self.config.maxLen}")
        logits, caches = self.prefill(prompts, lengths)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for _ in range(maxNewTokens - 1):   # token 0 came from prefill —
            logits, caches = self.decodeStep(tok, caches)   # N-1 steps
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        return np.stack([np.asarray(o) for o in out], axis=1)
