"""Keye-VL-2.0's language model for the serving tier, as ONE CHIP'S SHARE
of an expert-parallel deployment: a Qwen3-MoE decoder (grouped-query
attention with an RMSNorm on every query and key head and rotary
positions over the whole head, every layer an expert layer behind a
softmax router, no shared expert) whose attention reads only the rows a
learned SELECTOR picks, DeepSeek-V3.2-Exp's lightning indexer
(``sa_config``).  The vision tower is not here: text alone, for which the
three M-RoPE components are one position.

*Block.*  ``y = x + Attn(RMSNorm(x))``, ``out = y + MoE(RMSNorm(y))``, a
final RMSNorm and an untied head.

*Attention* on ``h = RMSNorm(x)``: ``q = h W_q`` in ``nHeads`` heads,
``k = h W_k``, ``v = h W_v`` in ``nKvHeads``; ``q <- RoPE(RMSNorm_q(q))``,
``k <- RoPE(RMSNorm_k(k))`` a head; query head ``a`` reads KV head ``a //
(nHeads / nKvHeads)``.

*Selector*, on the same ``h``: ``qI_j = RoPE(h W_qI)_j`` for ``indexHeads``
heads of ``indexSize``, ONE key ``kI = RoPE(LayerNorm(h W_kI))`` for all
of them, ``w = h W_w``; ``I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)``
in float32 over the real ``s <= t``.  Query ``t`` attends over the
``topk`` positions of largest ``I_{t,s}`` and no others (over all of them
while there are no more; of equal scores the earlier position).  So a
position keeps THREE rows: K, V and the index key, which
``cacheSpec()`` names (``CacheSpec.indexWidth``); the decode step reads
them through
:func:`~deeplearning4j_tpu.nn.conf.attention.paged_sparse_attention`.
The prefill computes the same selection for every query of a bucket
(:func:`sparse_attend_full`): on one TPU a kernel scores a block of
queries against every key and finds each query's ``topk``-th largest
score by bisection on the scores' bit patterns (the same set as a sort
gives, without one), and a flash kernel attends under that mask; both
read the sequence's first real position, and a tile of keys or of queries
that lies wholly before it, all left pads, is neither scored nor
attended: a prompt costs what its real tokens cost, not its bucket.  The
flash kernel's tile is a KV head's query heads (eight as served)
against 512 keys under ONE tile of the selection, added to the scores as
0 or ``-inf``; its online softmax keeps the running maximum the same in
every lane and the running sum as a partial sum a lane, so that a tile
crosses lanes once (``_flash_kernel``).  On the CPU or several devices the
same blocks in ``jax.numpy``.

*Rotary positions* pair lane ``i`` with lane ``i + D / 2``
(``served._rope``); a token's position is its index among the REAL
tokens: in a left-padded bucket ``p - start``, in the step ``pos -
start``.  A pad has no position and is no key.

*Expert layer.*  The router scores all ``nExperts`` in float32
(``parallel/moe.py:route_softmax_topk``), the ``expertsPerToken`` largest
are chosen, and this chip adds the part of the chosen experts it HOLDS;
what the absent ones would have added is left out (the deployment's
exchange would bring it).  The step reads only the held experts a live
slot's token chose (``moe_share_step``); forward and prefill multiply by
group (``moe_share_grouped``), 4,096 tokens at a time.  The routing's
three counts and the selector's two are taken on the device
(:data:`KeyeVLLM.stepCounters`) and come back behind the step's tokens.

Precision: weights, residual stream and the three kinds of row in the
parameters' dtype (bfloat16 as served); index scores, router, softmax,
norms, rotary angles and logits in float32; every matmul accumulates in
float32.  The index queries enter their matmul whole (three bfloat16
pieces), against the index keys as they are stored.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir

from deeplearning4j_tpu.nn.conf.attention import (CacheSpec, _INT_MIN,
                                                  _mxu_dot, _order_key,
                                                  _select_mask,
                                                  lowered_for_one_tpu,
                                                  paged_sparse_attention)
from deeplearning4j_tpu.nlp.mamba import _mm, _rms
from deeplearning4j_tpu.nlp.served import JitByLength, ServedLM, _rope
from deeplearning4j_tpu.parallel.moe import (moe_share_counts,
                                             moe_share_grouped,
                                             moe_share_step,
                                             route_softmax_topk)

__all__ = ["KeyeVLConfig", "KeyeVLLM", "sparse_attend_full"]

_F32 = jnp.float32
_I32 = jnp.int32
_NEG = -1e30
_ROUTING = ("moe_pairs_routed", "moe_pairs_absent", "moe_experts_hit")
_SELECTOR = ("sparse_rows_scored", "sparse_rows_selected")
_TILES = ("sparse_prefill_tiles_causal", "sparse_prefill_tiles_visited")
#: a prefill's two counts of the selector are quadratic in the prompt
#: (5.4e8 pairs scored a layer at 32,768), and sixteen admissions can
#: wait for one step: each rides as two int32 columns, the count's high
#: part in units of this and its low part in ones
_COUNT_UNIT = 1 << 16
#: tokens a pass of the grouped expert layer takes in forward and prefill:
#: ``moe_share_grouped`` brings each pair's output home through a
#: ``(tokens, tokens)`` 0/1 matrix, 2 GB and 4.4 TFLOP a layer at 32,768
_MOE_BLOCK = 4096
#: queries and keys a block of the full-sequence selection and attention
#: holds (the kernels' tiles; the ``jax.numpy`` form takes the same query
#: blocks against every key)
_QUERY_BLOCK = 128
_KEY_BLOCK = 512
#: lanes of a vector register: the flash kernel keeps a row's softmax sum
#: as this many partial sums (``_KEY_BLOCK`` is a multiple)
_LANES = 128


@dataclasses.dataclass
class KeyeVLConfig:
    vocabSize: int = 256
    nLayers: int = 2
    hiddenSize: int = 64
    nHeads: int = 4
    nKvHeads: int = 2
    headSize: int = 16
    expertSize: int = 32
    nExperts: int = 16          # routed experts the router scores
    expertsPerToken: int = 4
    expertsHeld: Tuple[int, int] = (0, 4)   # [lo, hi): this chip's share
    indexHeads: int = 2         # the selector's query heads
    indexSize: int = 8          # lanes of an index query and of THE key
    topk: int = 8               # rows a query attends over
    ropeTheta: float = 1e7
    eps: float = 1e-6
    maxLen: int = 128           # positions a slot may hold (bucket + new)
    initializerRange: float = 0.02
    seed: int = 0
    dtype: str = "bfloat16"

    @property
    def nHeld(self) -> int:
        return self.expertsHeld[1] - self.expertsHeld[0]


# ----------------------------------------------------------------------
# the selection over whole sequences
# ----------------------------------------------------------------------
def _index_scores(qI, wI, kI):
    """``I (b, q, k)`` float32 for index queries ``qI (b, q, hI, dI)``
    float32 with weights ``wI (b, q, hI)`` against the keys ``kI (b, k,
    dI)`` as they are stored."""
    s = jnp.einsum("bqjd,bkd->bqjk", qI, kI.astype(_F32),
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("bqj,bqjk->bqk", wI, jnp.maximum(s, _F32(0)),
                      precision=jax.lax.Precision.HIGHEST)


def _sparse_full_blocked(q, k, v, qI, wI, kI, start, *, topk):
    """The ``jax.numpy`` form of :func:`sparse_attend_full`: a block of
    queries at a time against every key, the selection as a mask."""
    b, T, H, dh = q.shape
    G = k.shape[2]
    r = H // G
    B = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T
    kpos = jnp.arange(T, dtype=_I32)[None, None, :]
    real = kpos >= start[:, None, None]                      # (b, 1, T)
    q5 = q.reshape(b, T, G, r, dh)

    def block(i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * B, B, axis=1)
        rows = i * B + jnp.arange(B, dtype=_I32)
        valid = (kpos <= rows[None, :, None]) & real         # (b, B, T)
        keep = _select_mask(_index_scores(cut(qI), cut(wI), kI), valid,
                            topk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", cut(q5), k,
                       preferred_element_type=_F32) * _F32(dh ** -0.5)
        a = jax.nn.softmax(jnp.where(keep[:, None, None], s, _F32(_NEG)),
                           axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", a.astype(v.dtype), v,
                          preferred_element_type=_F32)
    o = jax.lax.map(block, jnp.arange(T // B, dtype=_I32))
    return jnp.moveaxis(o, 0, 1).reshape(b, T, H * dh).astype(v.dtype)


# -- the kernels: the mask of a block of queries, and attention under it --
def _select_kernel(start_ref, q_ref, w_ref, k_ref, o_ref, key_ref, *, Bq,
                   Bk, hI, topk):
    """One block of ``Bq`` queries of one sequence: their index scores
    against every key from the chunk that holds the sequence's first real
    position up to the block's last query, ``Bk`` keys a chunk (``q_ref
    (hI Bq, dI)`` float32, head-major: row ``j Bq + t``; ``k_ref (T,
    dI)`` the stored keys), kept as ordered int32 keys in ``key_ref
    (chunks, Bq, Bk)``; each query's ``topk``-th largest by bisection;
    the selection written as int8 into ``o_ref (chunks, Bq, Bk)``.  The
    chunks of pads alone, those behind the block's last query and a block
    of pad queries alone are not touched."""
    b, i = pl.program_id(0), pl.program_id(1)
    s0 = start_ref[b]
    q0 = i * _I32(Bq)
    first = jax.lax.div(s0, _I32(Bk))                           # chunks
    live = jax.lax.div(q0 + _I32(Bq - 1), _I32(Bk)) + _I32(1)
    qpos = q0 + jax.lax.broadcasted_iota(_I32, (Bq, Bk), 0)
    lane = jax.lax.broadcasted_iota(_I32, (Bq, Bk), 1)
    zero = jnp.zeros((Bq, 1), _F32)      # counts ride as float32: exact

    def score(c, carry):
        rows = k_ref[pl.ds(pl.multiple_of(c * _I32(Bk), Bk), Bk), :]
        sc = _mxu_dot(q_ref[...], rows, (((1,), (1,)), ((), ())))
        acc = jnp.zeros((Bq, Bk), _F32)
        for j in range(hI):
            acc = acc + jnp.maximum(sc[j * Bq:(j + 1) * Bq], _F32(0)) \
                * w_ref[:, j:j + 1]
        kpos = c * _I32(Bk) + lane
        # jaxlint: disable=tracer-escape -- a Pallas ref: the store is the kernel's write to VMEM, run every iteration
        key_ref[c] = jnp.where((kpos >= s0) & (kpos <= qpos),
                               _order_key(acc), _I32(_INT_MIN))
        return carry

    def count(pred):
        """How many keys of a query satisfy ``pred(key chunk)``."""
        return jax.lax.fori_loop(
            first, live,
            lambda c, n: n + jnp.sum(pred(key_ref[c]).astype(_F32),
                                     axis=1, keepdims=True), zero)

    @pl.when(q0 + _I32(Bq - 1) >= s0)
    def _():
        jax.lax.fori_loop(first, live, score, _I32(0))
        k = _F32(topk)
        th = jnp.where(count(lambda x: x >= _I32(0)) >= k, _I32(0),
                       _I32(_INT_MIN))

        def bit(n, th):
            cand = th + jnp.left_shift(_I32(1), _I32(30) - n)
            return jnp.where(count(lambda x: x >= cand) >= k, cand, th)
        th = jax.lax.fori_loop(_I32(0), _I32(31), bit, th)
        need = k - count(lambda x: x > th)
        held = th > _I32(_INT_MIN)
        # a chunk's ties counted up to each lane: a 0/1 matmul on the MXU
        upto = (jax.lax.broadcasted_iota(_I32, (Bk, Bk), 0)
                <= jax.lax.broadcasted_iota(_I32, (Bk, Bk), 1)
                ).astype(jnp.bfloat16)

        def write(c, seen):
            key = key_ref[c]
            tie = (key == th) & held
            rank = seen + jax.lax.dot_general(
                tie.astype(jnp.bfloat16), upto, (((1,), (0,)), ((), ())),
                preferred_element_type=_F32)
            # jaxlint: disable=tracer-escape -- a Pallas ref, as above: the kernel's output block
            o_ref[c] = ((key > th) | (tie & (rank <= need))).astype(jnp.int8)
            return seen + jnp.sum(tie.astype(_F32), axis=1, keepdims=True)
        jax.lax.fori_loop(first, live, write, zero)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_call(start, qI, wI, kI, *, topk, interpret):
    """``mask (b, T / Bq, T / Bk, Bq, Bk)`` int8 for index queries ``qI
    (b, T, hI, dI)`` float32, weights ``wI (b, T, hI)`` and stored keys
    ``kI (b, T, dI)``: a tile is 1 where the query reads the key."""
    b, T, hI, dI = qI.shape
    Bq, Bk = _QUERY_BLOCK, _KEY_BLOCK
    nQ, nK = T // Bq, T // Bk
    # head-major rows inside a block: (b, nQ, hI * Bq, dI)
    qh = qI.reshape(b, nQ, Bq, hI, dI).swapaxes(2, 3).reshape(
        b, nQ, hI * Bq, dI)
    return pl.pallas_call(
        functools.partial(_select_kernel, Bq=Bq, Bk=Bk, hI=hI, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nQ),
            in_specs=[
                pl.BlockSpec((None, None, hI * Bq, dI),
                             lambda b, i, *_: (b, i, i * 0, i * 0)),
                pl.BlockSpec((None, None, Bq, hI),
                             lambda b, i, *_: (b, i, i * 0, i * 0)),
                pl.BlockSpec((None, T, dI),
                             lambda b, i, *_: (b, i * 0, i * 0))],
            out_specs=pl.BlockSpec(
                (None, None, nK, Bq, Bk),
                lambda b, i, *_: (b, i, i * 0, i * 0, i * 0)),
            scratch_shapes=[pltpu.VMEM((nK, Bq, Bk), _I32)]),
        out_shape=jax.ShapeDtypeStruct((b, nQ, nK, Bq, Bk), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 << 20),
        name="sparse_prefill_select",
        interpret=interpret,
    )(start.astype(_I32), qh, wI.reshape(b, nQ, Bq, hI), kI)


def _causal_tiles(T: int) -> int:
    """The ``(_QUERY_BLOCK, _KEY_BLOCK)`` tiles at or under the diagonal
    of ``T`` positions."""
    return sum((min(q0 + _QUERY_BLOCK, T) - 1) // _KEY_BLOCK + 1
               for q0 in range(0, T, _QUERY_BLOCK))


@functools.partial(jax.jit, static_argnames=("T",))
def _live_tiles(start, *, T):
    """The flash kernel's grid steps for sequences of ``T`` positions
    whose first real ones are ``start (b,)``: query block after
    query block, each with its key blocks from the one that holds
    ``start`` up to its diagonal; a block of pad queries alone keeps one
    step, which runs no tile.  ``(tile, flag)``, each ``(b * steps,)``
    int32 with room for every tile at or under the diagonal: ``tile`` is
    ``query block * key blocks + key block``; ``flag`` adds 1 where the
    step opens its query block, 2 where it closes it and 4 where it runs
    its tile.  The steps past the live ones repeat the last one's tile
    under flag 0: they fetch nothing.  A few small integer ops, the same
    for every layer: a jit of its own, as ``_work_list``."""
    Bq, Bk = _QUERY_BLOCK, _KEY_BLOCK
    qEnd = jnp.arange(Bq - 1, T, Bq, dtype=_I32)[None, :]    # (1, nQ)
    last = qEnd // Bk
    s0 = start[:, None]
    real = qEnd >= s0                                        # (b, nQ)
    lo = jnp.where(real, s0 // Bk, last)
    ends = jnp.cumsum(last - lo + 1, axis=1, dtype=_I32)
    at = jnp.arange(_causal_tiles(T), dtype=_I32)[None, :]   # (1, steps)
    i = jnp.minimum(jnp.sum(at[:, :, None] >= ends[:, None, :], axis=2,
                            dtype=_I32), T // Bq - 1)
    of = lambda a: jnp.take_along_axis(jnp.broadcast_to(a, real.shape), i,
                                       axis=1)
    c = jnp.minimum(of(lo) + at - of(ends - (last - lo + 1)), of(last))
    flag = jnp.where(at < ends[:, -1:], (c == of(lo)) * 1
                     + (c == of(last)) * 2 + of(real) * 4, 0)
    return ((i * (T // Bk) + c).reshape(-1).astype(_I32),
            flag.reshape(-1).astype(_I32))


def _flash_kernel(tile_ref, flag_ref, q_ref, k_ref, v_ref, keep_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, steps, r, scale):
    """One step of :func:`_live_tiles`, a (query block, key block) of one
    KV head: the ``r`` query heads of the group ride as ``r Bq`` rows
    (head-major), all under the one ``(Bq, Bk)`` tile of the selection;
    softmax online across a query block's key blocks, in float32.  A
    block of pad queries alone runs no tile and writes zeros.

    A tile's scores are ``(r Bq, Bk)`` float32, 2 MB, and what it costs
    beside its two matmuls is what crosses LANES (measured alone, PERF.md
    §5): so a tile reduces over lanes once, for the row maximum, and
    broadcasts nothing along them.

    * The selection is ONE additive tile for the ``r`` heads: 0 where
      ``keep`` is set, ``-inf`` where not, added to the scores seen as
      ``(r, Bq, Bk)``.  ``m`` starts at the finite ``_NEG``, so beside it
      a masked score gives ``exp(-inf - m) = 0`` exactly and
      ``max(m, -inf) = m``: no second mask, and a row that has met no kept
      key yet keeps ``m = _NEG``, ``l = 0``, ``acc = 0`` (a tile with no
      kept key multiplies them by ``exp(0) = 1``).  With ``-inf`` for the
      start too the first such tile would give ``exp(nan)``.
    * ``m_ref (r Bq, _LANES)`` holds each row's running maximum in the
      scores' OWN units, the same in every lane, so ``s - m`` takes a lane
      tile of scores against it elementwise.  The scale sits inside the
      exponent, ``p = exp((s - m) scale)``: a float32 factor on float32
      differences, never folded into the bfloat16 queries.
    * ``l_ref (r Bq, _LANES)`` holds each row's sum as ``_LANES`` PARTIAL
      sums, lane ``j`` the keys ``j, j + _LANES, ...`` of every block:
      ``shrink`` is a row's one factor for all of them, so the lanes are
      added up once, when the query block closes.
    """
    flag = flag_ref[pl.program_id(0) * _I32(steps) + pl.program_id(2)]
    has = lambda bit: jnp.bitwise_and(flag, _I32(bit)) != _I32(0)

    @pl.when(has(1))
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    @pl.when(has(4))
    def _():
        Bq, Bk = keep_ref.shape
        W = m_ref.shape[1]
        bias = jnp.where(keep_ref[...].astype(_I32) > _I32(0), _F32(0),
                         _F32(-jnp.inf))                     # (Bq, Bk)
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32)                     # (r Bq, Bk)
        s = (s.reshape(r, Bq, Bk) + bias[None]).reshape(r * Bq, Bk)
        mOld = m_ref[...]                                    # (r Bq, W)
        mNew = jnp.maximum(mOld, jnp.max(s, axis=-1, keepdims=True))
        shrink = jnp.exp((mOld - mNew) * _F32(scale))
        p = [jnp.exp((s[:, c:c + W] - mNew) * _F32(scale))
             for c in range(0, Bk, W)]
        l_ref[...] = shrink * l_ref[...] + functools.reduce(jnp.add, p)
        m_ref[...] = mNew
        acc_ref[...] = shrink[:, :1] * acc_ref[...] + jax.lax.dot_general(
            jnp.concatenate([x.astype(v_ref.dtype) for x in p], axis=1),
            v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    @pl.when(has(2))
    def _():
        # a pad query reads nothing: zeros, not 0 / 0
        l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, _F32(1e-30))
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flash_call(start, q, k, v, keep, *, interpret):
    """Attention of ``q (b, T, H, dh)`` over ``k, v (b, T, G, dh)`` under
    ``keep`` (:func:`_select_call`'s tiles) for sequences whose first
    real position is ``start (b,)``: ``(b, T, H dh)``.  The grid is
    :func:`_live_tiles`'s steps a KV head: no step behind the diagonal,
    none before ``start``."""
    b, T, H, dh = q.shape
    G = k.shape[2]
    r = H // G
    Bq, Bk = _QUERY_BLOCK, _KEY_BLOCK
    nQ, nK = T // Bq, T // Bk
    steps = _causal_tiles(T)
    # (b, G, nQ, r * Bq, dh): a group's heads as rows of one block
    q5 = q.reshape(b, nQ, Bq, G, r, dh).transpose(0, 3, 1, 4, 2, 5).reshape(
        b, G, nQ, r * Bq, dh)
    kv = lambda a: a.transpose(0, 2, 1, 3)                   # (b, G, T, dh)
    block = lambda b, n, tile: jax.lax.div(tile[b * steps + n], _I32(nK))
    chunk = lambda b, n, tile: jax.lax.rem(tile[b * steps + n], _I32(nK))
    rows = pl.BlockSpec((None, None, None, r * Bq, dh),
                        lambda b, g, n, tile, _: (b, g, block(b, n, tile),
                                                  n * 0, n * 0))
    keys = pl.BlockSpec((None, None, Bk, dh),
                        lambda b, g, n, tile, _: (b, g, chunk(b, n, tile),
                                                  n * 0))
    o = pl.pallas_call(
        functools.partial(_flash_kernel, steps=steps, r=r,
                          scale=dh ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, G, steps),
            in_specs=[rows, keys, keys,
                      pl.BlockSpec((None, None, None, Bq, Bk),
                                   lambda b, g, n, tile, _: (
                                       b, block(b, n, tile),
                                       chunk(b, n, tile), n * 0, n * 0))],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((r * Bq, _LANES), _F32),
                            pltpu.VMEM((r * Bq, _LANES), _F32),
                            pltpu.VMEM((r * Bq, dh), _F32)]),
        out_shape=jax.ShapeDtypeStruct((b, G, nQ, r * Bq, dh), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="sparse_prefill_attention",
        interpret=interpret,
    )(*_live_tiles(start.astype(_I32), T=T), q5, kv(k), kv(v), keep)
    return o.reshape(b, G, nQ, r, Bq, dh).transpose(0, 2, 4, 1, 3, 5).reshape(
        b, T, H * dh)


def _sparse_full_kernels(q, k, v, qI, wI, kI, start, *, topk,
                         interpret=False):
    """:func:`sparse_attend_full` as two Pallas TPU kernels: the
    selection's tiles, then flash attention under them; both read
    ``start`` and visit only the tiles that hold a real key under a real
    query.  ``interpret`` is for tests (the CPU)."""
    keep = _select_call(start, qI, wI, kI, topk=topk, interpret=interpret)
    return _flash_call(start, q, k, v, keep, interpret=interpret)


def _sparse_full_lowering(ctx, *args, topk):
    T = ctx.avals_in[0].shape[1]
    kernel = lowered_for_one_tpu(ctx) and T % _QUERY_BLOCK == 0 \
        and T % _KEY_BLOCK == 0
    return mlir.lower_fun(
        functools.partial(
            _sparse_full_kernels if kernel else _sparse_full_blocked,
            topk=topk), multiple_results=False)(ctx, *args)


_sparse_full_p = jex_core.Primitive("sparse_attend_full")


@functools.partial(jax.jit, static_argnames=("topk",))
def _sparse_full_eager(*args, topk):
    return _sparse_full_p.bind(*args, topk=topk)


_sparse_full_p.def_impl(_sparse_full_eager)
_sparse_full_p.def_abstract_eval(
    lambda q, k, v, *_, topk: jax.core.ShapedArray(
        q.shape[:2] + (q.shape[2] * q.shape[3],), v.dtype))
mlir.register_lowering(_sparse_full_p, _sparse_full_lowering)


def sparse_attend_full(q, k, v, qI, wI, kI, start, *, topk: int):
    """Causal attention over whole LEFT-padded sequences in which query
    ``t`` reads only the ``topk`` real positions ``s <= t`` of largest
    index score ``I_{t,s}`` (module docstring): ``q (b, T, H, dh)``, ``k,
    v (b, T, G, dh)`` in one dtype, ``qI (b, T, hI, dI)`` and ``wI (b, T,
    hI)`` float32, ``kI (b, T, dI)`` the index keys as stored, ``start
    (b,)`` the first real position.  Returns ``(b, T, H dh)`` in ``v``'s
    dtype; a pad query's row is not meaningful.  Lowered for one TPU, at
    lengths the tiles divide, as the two kernels, in which the key blocks
    and the query blocks wholly before ``start`` are neither scored nor
    attended (a real row does not depend on what lies there); elsewhere
    in ``jax.numpy`` (no knob: the rule of ``paged_attention``)."""
    return _sparse_full_p.bind(q, k, v, qI.astype(_F32), wI.astype(_F32),
                               kI, start.astype(_I32), topk=int(topk))


class KeyeVLLM(ServedLM):
    """The served model: ``forward`` (the recompute baseline), a bucketed
    left-padded prefill that also returns the three kinds of row and the
    counts, and the step form ``pagedLogits``, from which ``ServedLM``
    builds the scheduler's fixed-shape decode step and admission
    write."""

    #: what the step returns in the columns behind its tokens (row 0),
    #: for the batcher to add to ``serving_metrics()``: its own counts of
    #: the routing and of the selector, then those of the prefills since
    #: the step before (the selector's in two columns each, the first
    #: worth :data:`_COUNT_UNIT`, then the two counts of the prefill
    #: kernels' tiles)
    stepCounters = tuple(
        [(name, {"phase": "step"}) for name in _ROUTING + _SELECTOR]
        + [(name, {"phase": "prefill"}) for name in _ROUTING]
        + [(name, {"phase": "prefill"}, unit) for name in _SELECTOR
           for unit in (_COUNT_UNIT, 1)]
        + [(name, {"phase": "prefill"}) for name in _TILES])

    def __init__(self, config: Optional[KeyeVLConfig] = None, params=None,
                 **kw):
        self.config = c = config or KeyeVLConfig(**kw)
        lo, hi = c.expertsHeld
        if not 0 <= lo < hi <= c.nExperts or c.headSize % 2 \
                or c.indexSize % 2 or c.nHeads % c.nKvHeads:
            raise ValueError(
                f"expertsHeld {c.expertsHeld} names no share of "
                f"{c.nExperts} experts, a rotated width ({c.headSize}, "
                f"{c.indexSize}) is odd, or {c.nKvHeads} KV heads do not "
                f"divide {c.nHeads}")
        self.params = params if params is not None else self._init_params()

    # ------------------------------------------------------------------
    def _init_params(self) -> Dict:
        """Seeded weights drawn ON THE DEVICE in the configured dtype;
        only the held experts exist."""
        c = self.config
        dt = jnp.dtype(c.dtype)
        d, dh, f, n = c.hiddenSize, c.headSize, c.expertSize, c.nHeld
        std = c.initializerRange

        @jax.jit
        def layer(key):
            keys = iter(jax.random.split(key, 16))
            normal = lambda *shape: (std * jax.random.normal(
                next(keys), shape, _F32)).astype(dt)
            ones = lambda n: jnp.ones((n,), dt)
            return {"norm1": ones(d), "norm2": ones(d), "qnorm": ones(dh),
                    "knorm": ones(dh), "Wq": normal(d, c.nHeads * dh),
                    "Wk": normal(d, c.nKvHeads * dh),
                    "Wv": normal(d, c.nKvHeads * dh),
                    "Wo": normal(c.nHeads * dh, d),
                    "WqI": normal(d, c.indexHeads * c.indexSize),
                    "WkI": normal(d, c.indexSize),
                    "kInorm": ones(c.indexSize),
                    "kIbias": jnp.zeros((c.indexSize,), dt),
                    "Ww": normal(d, c.indexHeads),
                    "Wr": normal(d, c.nExperts), "Eg": normal(n, d, f),
                    "Eu": normal(n, d, f), "Ed": normal(n, f, d)}

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
                "layers": [layer(jax.random.fold_in(key, i + 1))
                           for i in range(c.nLayers)]}

    # ------------------------------------------------------------------
    def cacheSpec(self) -> CacheSpec:
        """What each layer keeps between steps, for the scheduler's pool:
        a K row, a V row and an index row a position in every layer, and
        beside them the counts that the prefills leave for the next step
        to return."""
        c = self.config
        return CacheSpec(
            pagedLayers=c.nLayers, kvHeads=c.nKvHeads, headSize=c.headSize,
            dtype=jnp.dtype(c.dtype), indexWidth=c.indexSize,
            slotState=(("counts", (1, len(_ROUTING) + 2 * len(_SELECTOR)
                                   + len(_TILES)), _I32),))

    # -- pieces shared by the full-sequence and the step forms ----------
    def _qkv(self, lp, h, p):
        """``(q (..., H, dh), k, v (..., G, dh))`` from ``h (..., d)`` at
        positions ``p (...)``: ``q`` and ``k`` normed a head and rotated,
        float32; ``v`` as projected."""
        c = self.config
        heads = lambda a, n: a.reshape(a.shape[:-1] + (n, c.headSize))
        turned = lambda a, g: _rope(_rms(a, g, c.eps), p[..., None],
                                    c.ropeTheta)
        return (turned(heads(_mm(h, lp["Wq"]), c.nHeads), lp["qnorm"]),
                turned(heads(_mm(h, lp["Wk"]), c.nKvHeads), lp["knorm"]),
                heads(_mm(h, lp["Wv"]), c.nKvHeads))

    def _index(self, lp, h, p):
        """The selector's ``(qI (..., hI, dI), wI (..., hI), kI (...,
        dI))`` float32 from ``h (..., d)`` at positions ``p (...)``."""
        c = self.config
        qI = _mm(h, lp["WqI"])
        qI = _rope(qI.reshape(qI.shape[:-1] + (c.indexHeads, c.indexSize)),
                   p[..., None], c.ropeTheta)
        k = _mm(h, lp["WkI"])
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                              + c.eps) * lp["kInorm"].astype(_F32) \
            + lp["kIbias"].astype(_F32)
        return qI, _mm(h, lp["Ww"]), _rope(k, p, c.ropeTheta)

    def _moe(self, lp, h, real, grouped: bool):
        """``(MoE(h), counts (3,))`` for ``h (T, d)`` float32: this chip's
        part of the chosen experts, and the routing's counts over the
        ``real (T,)`` tokens."""
        c = self.config
        lo = c.expertsHeld[0]
        idx, w = route_softmax_topk(h, lp["Wr"], c.expertsPerToken)
        experts = (lp["Eg"], lp["Eu"], lp["Ed"], lo)
        T = h.shape[0]
        if not grouped:
            out = moe_share_step(h, idx, w, *experts, real)
        elif T > _MOE_BLOCK and T % _MOE_BLOCK == 0:
            cut = lambda a: a.reshape((T // _MOE_BLOCK, _MOE_BLOCK)
                                      + a.shape[1:])
            out = jax.lax.map(
                lambda a: moe_share_grouped(*a[:3], *experts, a[3]),
                (cut(h), cut(idx), cut(w), cut(real))).reshape(T, -1)
        else:
            out = moe_share_grouped(h, idx, w, *experts, real)
        return out, moe_share_counts(idx, lo, c.nHeld, real)

    def _logits(self, params, x):
        return _mm(_rms(x, params["normf"], self.config.eps), params["head"])

    def _prefill_selector_counts(self, n):
        """The selector's counts of prefills of ``n (b,)`` real tokens, as
        the slot state keeps them: ``[scored high, scored low, selected
        high, selected low]`` int32.  A layer scores ``n (n + 1) / 2``
        pairs and reads ``min(t + 1, topk)`` rows for its ``t``-th
        query."""
        c = self.config
        k = jnp.minimum(n, c.topk)
        scored = n * (n + 1) // 2
        selected = k * (k + 1) // 2 + (n - k) * k
        parts = [part * c.nLayers for count in (scored, selected)
                 for part in (jnp.sum(count // _COUNT_UNIT),
                              jnp.sum(count % _COUNT_UNIT))]
        return jnp.stack(parts).astype(_I32)

    def _prefill_tile_counts(self, start, T: int):
        """``[causal, visited]`` int32 over the layers for prefills of a
        bucket of ``T`` whose first real positions are ``start (b,)``:
        the ``(_QUERY_BLOCK, _KEY_BLOCK)`` tiles at or under the bucket's
        diagonal, and those of them that hold a real key under a real
        query, which are the ones :func:`sparse_attend_full`'s kernels
        visit."""
        q0 = jnp.arange(0, T, _QUERY_BLOCK, dtype=_I32)[None, :]
        qEnd = jnp.minimum(q0 + _QUERY_BLOCK, T) - 1         # (1, blocks)
        s0 = start[:, None]
        visited = jnp.where(qEnd >= s0,
                            qEnd // _KEY_BLOCK - s0 // _KEY_BLOCK + 1, 0)
        return self.config.nLayers * jnp.stack(
            [start.shape[0] * _causal_tiles(T), jnp.sum(visited)]
        ).astype(_I32)

    # ------------------------------------------------------------------
    # full-sequence form: forward and prefill
    # ------------------------------------------------------------------
    def _run_full(self, params, tokens, start):
        """``tokens (b, T)`` LEFT-padded, ``start (b,)`` the first real
        position.  Returns the last layer's output, every layer's rows as
        the step will read them (K and V ``(L, b, G, T, dh)``, index rows
        ``(L, b, 1, T, W)``) and the counts over the real tokens
        ``(9,)`` (the routing's three, the selector's two in two parts
        each, the two of the kernels' tiles)."""
        c = self.config
        b, T = tokens.shape
        at = jnp.arange(T, dtype=_I32)[None, :]
        real = at >= start[:, None]                          # (b, T)
        p = jnp.maximum(at - start[:, None], 0)
        x = params["emb"][tokens]
        cd = x.dtype
        W = self.cacheSpec().indexRowWidth
        kS = jnp.zeros((c.nLayers, b, c.nKvHeads, T, c.headSize), cd)
        vS = jnp.zeros_like(kS)
        iS = jnp.zeros((c.nLayers, b, 1, T, W), cd)
        routed = jnp.zeros((3,), _I32)
        hold = jax.lax.optimization_barrier
        for li, lp in enumerate(params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            q, k, v = self._qkv(lp, h, p)
            qI, wI, kI = self._index(lp, h, p)
            q, k, v, kI = (a.astype(cd) for a in (q, k, v, kI))
            kS = kS.at[li].set(k.swapaxes(1, 2))
            vS = vS.at[li].set(v.swapaxes(1, 2))
            iS = iS.at[li, :, 0, :, :c.indexSize].set(kI)
            o = sparse_attend_full(q, k, v, qI, wI, kI, start, topk=c.topk)
            # the stream is written out after every add (see
            # OlmoHybridLM._run_full)
            y = hold(x + _mm(o, lp["Wo"]).astype(cd))
            ff, n = self._moe(lp, _rms(y, lp["norm2"], c.eps
                                       ).reshape(b * T, -1),
                              real.reshape(-1), grouped=True)
            routed = routed + n
            x = hold(y + ff.reshape(b, T, -1).astype(cd))
        return x, (kS, vS, iS), jnp.concatenate(
            [routed, self._prefill_selector_counts((T - start).astype(_I32)),
             self._prefill_tile_counts(start, T)])

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
        """``(last logits (b, vocab), kStack, vStack, indexStack,
        counts)``: the rows in :func:`paged_rows_write`'s form and the
        counts ``(1, b, 9)`` in the pool's order (the whole batch's in
        every row: the scheduler prefills one sequence at a time)."""
        def run(params, tokens, start):
            x, rows, counts = self._run_full(params, tokens, start)
            b = tokens.shape[0]
            # the counts ride as slot state: (1 layer, b, 9), the batch
            # row's own where there is one row (the scheduler's case)
            return (self._logits(params, x[:, -1]), *rows,
                    jnp.broadcast_to(counts, (1, b) + counts.shape))
        return JitByLength(run, "prefill")

    # ------------------------------------------------------------------
    # step form — the continuous-batching scheduler's executables
    # ------------------------------------------------------------------
    def pagedLogits(self, params, poolK, poolV, poolI, counts, toks,
                    pageTable, pos, start):
        """One token per slot (``toks (S, 1)``) against the pool's arrays:
        ``((S, 1, vocab) logits, poolK, poolV, poolI, counts, counted
        (14,))``.  A slot whose ``pos`` is 0 holds no sequence (or is
        deferred a round): its rows land on the scratch page through its
        zeroed page table and it is not counted.  ``counted`` are this
        step's five counts, then the nine columns that the prefills
        since the last step left in ``counts``, which comes back
        zeroed."""
        c = self.config
        S, tq = toks.shape
        if tq != 1:
            raise ValueError(
                "the step takes one token a slot: speculative "
                "verification (tq > 1) would need a position a query")
        active = pos > 0
        p = jnp.maximum(pos - start, 0)
        x = params["emb"][toks[:, 0]]                         # (S, d)
        cd = x.dtype
        routed = jnp.zeros((3,), _I32)
        for li, lp in enumerate(params["layers"]):
            h = _rms(x, lp["norm1"], c.eps)
            q, k, v = self._qkv(lp, h, p)                     # (S, ., dh)
            qI, wI, kI = self._index(lp, h, p)
            ctx, poolK, poolV, poolI = paged_sparse_attention(
                q[:, :, None], k[:, :, None], v[:, :, None], qI, wI, kI,
                poolK, poolV, poolI, li, pageTable, pos, start,
                topk=c.topk)
            y = x + _mm(ctx.reshape(S, -1), lp["Wo"]).astype(cd)
            ff, n = self._moe(lp, _rms(y, lp["norm2"], c.eps), active,
                              grouped=False)
            routed = routed + n
            x = y + ff.astype(cd)
        live = jnp.where(active, p + 1, 0)
        seen = c.nLayers * jnp.stack(
            [jnp.sum(live), jnp.sum(jnp.minimum(live, c.topk))]).astype(_I32)
        left = jnp.sum(counts, axis=(0, 1)).astype(_I32)
        return (self._logits(params, x)[:, None], poolK, poolV, poolI,
                jnp.zeros_like(counts),
                jnp.concatenate([routed, seen, left]))
