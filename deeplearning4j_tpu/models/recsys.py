"""Recommender-tier models: DLRM-style feature interaction, two-tower
retrieval scoring, and the paged top-k serving adapter.

Training side: two DSL layers that sit on top of
:class:`~deeplearning4j_tpu.nn.conf.embedding.ShardedEmbeddingBag` —
``FeatureInteractionLayer`` (the DLRM pairwise-dot interaction over
field embeddings) and ``DotProductScorer`` (the two-tower affinity head
with binary cross-entropy).  Both are plain registered layers, so the
recommender nets train through the standard ``MultiLayerNetwork`` /
``MeshTrainer`` / ``FaultTolerantTrainer`` stack with the table
row-sharded over the ``model`` axis.

Serving side: :class:`RetrievalLM` adapts top-k retrieval onto
``ContinuousBatcher``'s paged-LM executor contract.  A retrieval
request IS a short generative sequence:

- "vocabulary"  = the item corpus (ids share the hashed feature space);
- "prompt"      = the user's hashed feature ids;
- prefill       = user-tower pooling → query embedding ``u``; the
                  prompt logits are ``u · itemsᵀ``, so the scheduler's
                  admission-time argmax emits rank 1;
- one decode step = one retrieval rank: the step reads ``u`` back from
  the K pool, re-scores the corpus, masks every already-emitted item
  (reconstructed from the V pool pages, where each emitted item id is
  written as the "token" value), and emits the next-best item;
- ``maxNewTokens = k`` streams the top-k ranks.

A k=1 request emits at admission and retires before ever entering the
decode batch — the single-step shape that bypasses KV-page shedding in
``AdmissionControl`` and the admit/retire-churn stress case the paged
scheduler was built for.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.attention import CacheSpec
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import BaseLayer, register_layer
from deeplearning4j_tpu.nn.lossfunctions import get_loss
from deeplearning4j_tpu.nlp.served import ServedLM

__all__ = ["FeatureInteractionLayer", "DotProductScorer",
           "RetrievalConfig", "RetrievalLM", "topk_retrieve"]

# score mask for already-emitted items: finite (NaN-free through any
# downstream softmax) but below any real dot-product score
_NEG_INF = -1e30


@register_layer
@dataclasses.dataclass
class FeatureInteractionLayer(BaseLayer):
    """DLRM-style pairwise feature interaction.

    Input (FF): (b, numFields * embeddingDim) concatenated field
    embeddings (the output of a ``ShardedEmbeddingBag`` with
    ``numFields`` fields).  Output: the input concatenated with the
    upper-triangle pairwise dot products — (b, numFields*embeddingDim +
    numFields*(numFields-1)/2).  Parameter-free; the interaction
    indices are static so the fused step never re-traces.
    """
    numFields: int = 0
    embeddingDim: int = 0

    def preferredFormat(self):
        return "FF"

    def inferNIn(self, inputType):
        if not self.embeddingDim:
            if not self.numFields or inputType.size % self.numFields:
                raise ValueError(
                    f"input size {inputType.size} not divisible by "
                    f"numFields {self.numFields}")
            self.embeddingDim = inputType.size // self.numFields

    def getOutputType(self, inputType):
        f = self.numFields
        return InputType.feedForward(
            f * self.embeddingDim + f * (f - 1) // 2)

    def initParams(self, key, inputType, dtype=jnp.float32):
        return {}

    def forward(self, params, x, train, key, state):
        b = x.shape[0]
        e = x.reshape(b, self.numFields, self.embeddingDim)
        dots = jnp.einsum("bfd,bgd->bfg", e, e)
        iu, ju = jnp.triu_indices(self.numFields, k=1)
        inter = dots[:, iu, ju]
        return jnp.concatenate([x, inter], axis=1), state


@register_layer
@dataclasses.dataclass
class DotProductScorer(BaseLayer):
    """Two-tower affinity head: input (b, 2*embeddingDim) = user
    embedding | item embedding, output sigmoid(u·v) with binary
    cross-entropy loss.  Parameter-free — the towers' capacity lives in
    the (sharded) embedding table below it."""
    embeddingDim: int = 0
    lossFunction: str = "xent"

    def preferredFormat(self):
        return "FF"

    def inferNIn(self, inputType):
        if not self.embeddingDim:
            if inputType.size % 2:
                raise ValueError(
                    f"input size {inputType.size} must split into two "
                    "towers")
            self.embeddingDim = inputType.size // 2

    def getOutputType(self, inputType):
        return InputType.feedForward(1)

    def initParams(self, key, inputType, dtype=jnp.float32):
        return {}

    def hasLoss(self) -> bool:
        return True

    def computeScore(self, labels, output, mask=None):
        return get_loss(self.lossFunction)(labels, output, mask)

    def forward(self, params, x, train, key, state):
        u, v = jnp.split(x, 2, axis=1)
        s = (u * v).sum(axis=1, keepdims=True)
        return jax.nn.sigmoid(s), state


# ---------------------------------------------------------------------------
# paged top-k serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetrievalConfig:
    """The slice of the LM config surface ``ContinuousBatcher`` reads.

    One pseudo-layer, one pseudo-head of width ``embeddingDim``: the KV
    pool's K pages hold the user query embedding (broadcast to every
    prompt position so any pooled position recovers it) and the V pages
    hold emitted item ids (channel 0; -1 = none), giving the decode
    step everything it needs from pool state alone — preemption and
    re-admission replay retrieval state exactly like generative KV.
    """
    vocabSize: int          # item corpus == hashed feature id space
    embeddingDim: int
    maxLen: int             # prompt bucket + k must fit here
    nLayers: int = 1
    nHeads: int = 1

    @property
    def headSize(self) -> int:
        return self.embeddingDim


class RetrievalLM(ServedLM):
    """Top-k retrieval over an item corpus as a paged-decode "LM".

    ``userTable``/``itemTable`` are (vocabSize, embeddingDim) — for a
    shared-table two-tower model both are the trained
    ``ShardedEmbeddingBag`` weight (see :meth:`from_two_tower`).
    Scores are the plain dot products ``u · itemsᵀ`` where ``u`` is the
    mean of the user's hashed-feature embeddings; ranks are exact
    (bit-stable across decode steps: ``u`` round-trips the f32 pool
    unchanged, so every step re-derives identical corpus scores).
    """

    def __init__(self, userTable, itemTable, maxLen: int = 64):
        user = jnp.asarray(userTable, jnp.float32)
        items = jnp.asarray(itemTable, jnp.float32)
        if user.shape != items.shape:
            raise ValueError(
                f"tower tables disagree: {user.shape} vs {items.shape}")
        self.config = RetrievalConfig(
            vocabSize=int(user.shape[0]),
            embeddingDim=int(user.shape[1]), maxLen=int(maxLen))
        self.params = {"user": user, "items": items}

    @classmethod
    def from_two_tower(cls, net, layerKey: str = "0",
                       maxLen: int = 64) -> "RetrievalLM":
        """Serving snapshot of a trained two-tower net whose layer
        ``layerKey`` is the shared ``ShardedEmbeddingBag`` table."""
        W = net.params_[layerKey]["W"]
        return cls(W, W, maxLen=maxLen)

    def cacheSpec(self) -> CacheSpec:
        c = self.config
        return CacheSpec(c.nLayers, c.nHeads, c.headSize)

    # -- prefill --------------------------------------------------------
    @functools.cached_property
    def _prefillRawFn(self):
        """(b, t) LEFT-padded user-feature ids -> (corpus scores (b,
        vocab), kStack, vStack (1, b, 1, t, d))."""
        def run(params, tokens, start):
            b, t = tokens.shape
            d = params["user"].shape[1]
            kpos = jnp.arange(t, dtype=jnp.int32)[None, :]
            mask = (kpos >= start[:, None]).astype(jnp.float32)
            e = params["user"][tokens] * mask[..., None]
            u = e.sum(1) / jnp.maximum(mask.sum(1), 1.0)[:, None]
            logits = u @ params["items"].T
            # K: the query embedding at EVERY prompt position — the
            # decode step reads it back from page 0, position 0.
            # V: channel-0 item ids, -1 = "no item emitted here".
            kStack = jnp.broadcast_to(u[:, None, :], (b, t, d))[None, :,
                                                                None]
            vStack = jnp.full((1, b, 1, t, d), -1.0, jnp.float32)
            return logits, kStack, vStack
        return jax.jit(run)

    # -- decode ---------------------------------------------------------
    def pagedLogits(self, params, poolK, poolV, toks, pageTable, pos,
                    start):
        """One retrieval rank a slot: ``toks (S, 1)``, each slot's
        last-emitted item, against the token-major pools ``(1, numPages,
        pageSize, d)`` (one layer, one head: a row is one position's
        ``d`` channels) -> ``(scores (S, 1, vocab), poolK, poolV)``.  The
        step writes the item into the V pool at ``pos`` and scores the
        corpus with every item the pool says was already emitted masked
        out, so that the arg-max ``ServedLM``'s step takes of it is the
        next-ranked item."""
        S = toks.shape[0]
        ps = poolV.shape[2]
        rows = jnp.arange(S)
        # query embedding: position 0 of each slot's first page
        u = poolK[0, pageTable[:, 0], 0, :]             # (S, d)
        scores = u @ params["items"].T                  # (S, vocab)
        # emitted-item history from the V pool (channel 0 over every
        # held page position; prompt region holds -1 sentinels and
        # unwritten positions are gated by pos)
        hist = poolV[0, pageTable, :, 0].reshape(S, -1)
        posidx = jnp.arange(hist.shape[1], dtype=jnp.int32)
        emitted = jnp.where(posidx[None, :] < pos[:, None],
                            hist.astype(jnp.int32), -1)
        penalty = jnp.zeros_like(scores)
        # mode="drop": the -1 invalid markers scatter out of bounds
        penalty = penalty.at[
            rows[:, None], emitted].set(_NEG_INF, mode="drop")
        last = toks[:, -1]
        penalty = penalty.at[rows, last].set(_NEG_INF)
        # page in the last-emitted item at pos (inactive slots write
        # to the scratch page through their zeroed page tables)
        page = pageTable[rows, pos // ps]
        poolV = poolV.at[0, page, pos % ps, 0].set(
            last.astype(poolV.dtype))
        return (scores + penalty)[:, None], poolK, poolV


def topk_retrieve(batcher, userIds, k: int, timeout=None) -> np.ndarray:
    """Top-k item retrieval through a ``ContinuousBatcher`` wrapping a
    :class:`RetrievalLM`: (b, t) hashed user-feature ids -> (b, k) item
    ids ranked best-first.  Observes end-to-end latency into
    ``dl4j_tpu_recsys_topk_latency_seconds``."""
    from deeplearning4j_tpu.telemetry import recsys_metrics
    t0 = time.perf_counter()
    out = batcher.submit({"tokens": userIds, "maxNewTokens": int(k)},  # jaxlint: sync-ok -- k is a host request parameter, not a device scalar
                         timeout=timeout)
    recsys_metrics().topk_latency().observe(time.perf_counter() - t0)
    return out
