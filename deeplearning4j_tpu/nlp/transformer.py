"""Decoder-only transformer LM, served through a paged KV pool.

The training side of this repo already runs transformer encoders (the
SameDiff BERT of ``zoo/bert.py``, flash attention for long context);
serving generative traffic needs the *decode* discipline those graphs
don't have: generation re-run through a full forward is O(t) per token.
This model keeps decode O(1) per token by writing every layer's K/V into
the pages of a ``KVCachePool`` (``remote/scheduler.py``), with all
executable shapes STATIC:

- the prefill (``prefillRaw``, the base's wrapper of ``_prefillRawFn``)
  runs a LEFT-padded prompt bucket through the stack once (causal
  attention dispatching through ``parallel.ring.dot_product_attention``)
  and returns the per-layer K/V for the scheduler to copy into pool
  pages;
- :meth:`pagedLogits` feeds ONE token per slot against the pool
  (:func:`~deeplearning4j_tpu.nn.conf.attention.paged_attention`:
  lowered for one TPU it reads each slot's live pages where they lie,
  anywhere else it gathers the slot's capacity under a mask) — fixed
  (slots, page-table width) shapes, so the batcher warms one executable
  (:class:`~deeplearning4j_tpu.nlp.served.ServedLM` builds the step
  from it) and never re-traces in steady state;
- :meth:`forward` is the plain causal forward, and :meth:`generate` the
  greedy recompute over it: the reference the served path is held to.

Weights follow the pre-LN GPT block (LN → attention → residual, LN → FFN
→ residual) with tied input/output embeddings.

The tied table ``params["emb"]`` is HELD with its rows padded with zeros
to whole lane tiles of 128 columns (GPT-2 XL: 1,600 → 1,664), laid out
once, when a tree is given to the model (:func:`lane_aligned`).  A TPU
keeps a float32 ``(vocab, width)`` array whose width is no whole number
of lane tiles (1,600 is 12.5) VOCABULARY-minor in HBM, which suits the
head's product and not the lookup, which needs rows: every program that
does both then makes itself a row-minor copy of the whole table, 322 MB
a decode step and a prefill, however the product is written.  At whole
tiles the array lies row-minor and both read it as it lies.  The lookup
drops the pad columns and the head multiplies a zero-padded ``h``: the
sums gain exact zeros.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.attention import CacheSpec, paged_attention
from deeplearning4j_tpu.nlp.served import ServedLM

__all__ = ["TransformerLMConfig", "TransformerLM"]

#: columns of one lane tile of the TPU's (8, 128) layout
_LANES = 128
#: rows of the table that :func:`_pad_columns` moves at a time
_PAD_ROWS = 1024


@functools.partial(jax.jit, static_argnums=1)
def _pad_columns(emb, pad: int):
    """``emb (V, W)`` with ``pad`` zero columns behind each row, a block
    of rows at a time into a zero table that is updated in place.  (One
    ``jnp.pad`` of the whole table computes the same and holds a second
    table-sized temporary while it runs, 335 MB at GPT-2 XL's sizes: the
    TPU keeps a ``(V, 1600)`` array vocabulary-minor and a ``(V, 1664)``
    one row-minor, so the pad is a transposition.)"""
    V, W = emb.shape
    R = min(V, _PAD_ROWS)

    def block(i, out):
        r0 = jnp.minimum(i * R, V - R)      # the last block overlaps
        rows = jax.lax.dynamic_slice(emb, (r0, 0), (R, W))
        return jax.lax.dynamic_update_slice(
            out, jnp.pad(rows, ((0, 0), (0, pad))), (r0, 0))
    return jax.lax.fori_loop(0, -(-V // R), block,
                             jnp.zeros((V, W + pad), emb.dtype))


def lane_aligned(emb):
    """The tied table ``(vocab, width)`` with its rows padded with zeros
    to the next multiple of 128 columns; the array itself, nothing
    copied, where its width already is one."""
    pad = -emb.shape[1] % _LANES
    return _pad_columns(emb, pad) if pad else emb


@dataclasses.dataclass
class TransformerLMConfig:
    vocabSize: int = 256
    nLayers: int = 2
    nHeads: int = 4
    headSize: int = 16
    ffnMult: int = 4
    maxLen: int = 128          # positions: max prompt + generation
    initializerRange: float = 0.02
    seed: int = 0

    @property
    def hiddenSize(self) -> int:
        return self.nHeads * self.headSize


class TransformerLM(ServedLM):
    """GPT-style causal LM: one block body, attended causally over the
    sequence (``forward``, ``prefillRaw``) or against pool pages (the
    paged decode step)."""

    def __init__(self, config: Optional[TransformerLMConfig] = None, **kw):
        self.config = config or TransformerLMConfig(**kw)
        self.params = self._init_params()

    @property
    def params(self) -> Dict:
        """The parameter tree the programs are given.  Assigning a tree
        lays its tied table out in whole lane tiles
        (:func:`lane_aligned`), once; a tree whose table already is (the
        model's own, a re-placed one) is kept as it is."""
        return self._params

    @params.setter
    def params(self, tree: Dict) -> None:
        emb = lane_aligned(tree["emb"])
        self._params = tree if emb is tree["emb"] else {**tree, "emb": emb}

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        c = self.config
        rng = np.random.RandomState(c.seed)
        H, F = c.hiddenSize, c.ffnMult * c.hiddenSize

        def draw(*shape):
            return (rng.randn(*shape) * c.initializerRange).astype(np.float32)

        def init(*shape):
            return jnp.asarray(draw(*shape))

        # float32 spelled out on every leaf: the package enables x64, so
        # a dtype-less jnp.ones/zeros is float64 and promotes everything
        # after the first LayerNorm (a TPU has no f64 unit)
        f32 = jnp.float32
        # the tied table in whole lane tiles (the module's text says why),
        # padded on the host: the device is sent the one array it keeps
        p = {"emb": jnp.asarray(np.pad(draw(c.vocabSize, H),
                                       ((0, 0), (0, -H % _LANES)))),
             "pos": init(c.maxLen, H),
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

    def _block(self, lp, x, attend):
        """The pre-LN block: LayerNorm → Q/K/V → ``attend`` → ``Wo`` →
        residual, LayerNorm → FFN → residual.  ``attend(qh, kh, vh)``
        returns ``(ctx, k, v)``: the context and what the caller keeps
        of this layer's K/V (the heads themselves, or the pools they
        were written into); the block returns ``(x, k, v)``."""
        h = self._ln(x, lp["ln1_g"], lp["ln1_b"])
        qh = self._heads(jnp.matmul(h, lp["Wq"]))
        kh = self._heads(jnp.matmul(h, lp["Wk"]))
        vh = self._heads(jnp.matmul(h, lp["Wv"]))
        ctx, k, v = attend(qh, kh, vh)
        x = x + jnp.matmul(self._merge(ctx), lp["Wo"])
        h = self._ln(x, lp["ln2_g"], lp["ln2_b"])
        ff = jax.nn.gelu(jnp.matmul(h, lp["Wi"]) + lp["bi"])
        return x + jnp.matmul(ff, lp["Wp"]) + lp["bp"], k, v

    @staticmethod
    def _causal(mask):
        """``attend`` over the whole sequence (forward/prefill), through
        ``dot_product_attention``'s dispatch; keeps the K/V heads."""
        from deeplearning4j_tpu.parallel.ring import dot_product_attention
        return lambda qh, kh, vh: (dot_product_attention(
            qh, kh, vh, mask=mask, causal=True), kh, vh)

    def _embed(self, params, tokens, pos_ids):
        """Token rows + position rows, ``(b, t, H)``.  The table's rows
        are as wide as the array given (whole lane tiles where the model
        holds it, ``H`` where a caller passes the plain table): the
        first ``H`` columns of the rows looked up are the embedding, the
        rest are zeros."""
        x = params["emb"][tokens][..., :self.config.hiddenSize]
        return x + params["pos"][pos_ids]

    def _logits(self, params, x):
        """Final LayerNorm and the tied head: ``h`` padded with zeros to
        the width of the table it is given, contracted with the table's
        minor dimension (no transpose of the table is asked for).  The
        pad columns of both are zero, so the sums gain exact zeros."""
        h = self._ln(x, params["lnf_g"], params["lnf_b"])
        emb = params["emb"]
        h = jnp.pad(h, [(0, 0)] * (h.ndim - 1)
                    + [(0, emb.shape[1] - h.shape[-1])])
        return jax.lax.dot_general(
            h, emb, (((h.ndim - 1,), (1,)), ((), ())))

    # ------------------------------------------------------------------
    # full forward (the recompute baseline the paged path must match)
    # ------------------------------------------------------------------
    @functools.cached_property
    def _fwd(self):
        def run(params, tokens):
            t = tokens.shape[1]
            x = self._embed(params, tokens,
                            jnp.arange(t, dtype=jnp.int32)[None, :])
            for lp in params["layers"]:
                x, _, _ = self._block(lp, x, self._causal(None))
            return self._logits(params, x)
        return jax.jit(run)

    def forward(self, tokens) -> jax.Array:
        """Full causal forward: (b, t) int32 -> (b, t, vocab) logits."""
        return self._fwd(self.params, jnp.asarray(tokens, jnp.int32))

    # ------------------------------------------------------------------
    # paged decode — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    @functools.cached_property
    def _prefillRawFn(self):
        """Prefill of a (b, t) LEFT-padded prompt: the last logits (b,
        vocab) and the per-layer K/V heads STACKED ((nLayers, b, h, t,
        d)) — the continuous scheduler copies them straight into pool
        pages."""
        def run(params, tokens, start):
            # start[b] = index of the first REAL token (left padding);
            # position ids count from the real start so padded and
            # unpadded prompts see identical positional embeddings
            b, t = tokens.shape
            kpos = jnp.arange(t, dtype=jnp.int32)[None, :]
            pos_ids = jnp.maximum(kpos - start[:, None], 0)
            mask = (kpos >= start[:, None]).astype(jnp.float32)
            x = self._embed(params, tokens, pos_ids)
            ks, vs = [], []
            attend = self._causal(mask)
            for lp in params["layers"]:
                x, kh, vh = self._block(lp, x, attend)
                ks.append(kh)
                vs.append(vh)
            return (self._logits(params, x[:, -1:])[:, 0],
                    jnp.stack(ks), jnp.stack(vs))
        return jax.jit(run)

    def cacheSpec(self) -> CacheSpec:
        """What the layers keep between decode steps, for the
        scheduler's pool: every layer owns K/V pages, nothing else."""
        c = self.config
        return CacheSpec(c.nLayers, c.nHeads, c.headSize)

    def pagedLogits(self, params, poolK, poolV, toks, pageTable, pos,
                    start):
        """toks (S, tq) against the stacked pools (L, pages, pageSize,
        nHeads*headSize): returns ((S, tq, vocab) logits, pools) — what
        the paged decode step takes its arg-max of, and what a parity
        check compares with :meth:`forward`.  Every layer writes and
        reads the stacked pools in place.  Position-embedding ids are
        clipped so a row past ``maxLen`` can't index out of the table."""
        tq = toks.shape[1]
        pos_ids = jnp.clip(
            (pos - start)[:, None] + jnp.arange(tq, dtype=jnp.int32),
            0, self.config.maxLen - 1)
        x = self._embed(params, toks, pos_ids)
        for li, lp in enumerate(params["layers"]):
            # called inside _block, before the pools are rebound below
            x, poolK, poolV = self._block(
                lp, x, lambda qh, kh, vh: paged_attention(
                    qh, kh, vh, poolK, poolV, li, pageTable, pos, start))
        return self._logits(params, x), poolK, poolV

    # ------------------------------------------------------------------
    def generate(self, prompts, maxNewTokens: int) -> np.ndarray:
        """Greedy decode by recompute: (b, t) prompts -> (b,
        maxNewTokens) int32, each token the arg-max of :meth:`forward`
        over everything before it.  No cache of any kind: this is the
        reference the served path (``ContinuousBatcher``) is held to,
        and it costs a full forward a token.  Every forward runs on one
        ``(b, maxLen)`` right-padded shape (causal, so no real position
        attends the pad): one compile whatever the lengths.

        Capacity check: t + maxNewTokens must fit ``maxLen``."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None, :]
        b, t = prompts.shape
        if t + maxNewTokens > self.config.maxLen:
            raise ValueError(
                f"prompt {t} + maxNewTokens {maxNewTokens} exceeds "
                f"capacity {self.config.maxLen}")
        toks = np.zeros((b, self.config.maxLen), np.int32)
        toks[:, :t] = prompts
        for i in range(t, t + maxNewTokens):
            greedy = jnp.argmax(self.forward(toks), axis=-1)
            toks[:, i] = np.asarray(greedy)[:, i - 1]
        return toks[:, t:t + maxNewTokens]
