"""Attention layers.

Reference: deeplearning4j-nn ``conf/layers/{SelfAttentionLayer,
LearnedSelfAttentionLayer,RecurrentAttentionLayer}.java`` wrapping the
libnd4j fused ``multi_head_dot_product_attention`` declarable op
(``ops/declarable/generic/nn/multi_head_dot_product_attention.cpp`` —
SURVEY.md §2.5, §5.7).

TPU-first: attention is ONE einsum chain (projections → scores → softmax →
context → out-projection), fully fused by XLA onto the MXU — no custom-op
dispatch.  Data format follows the DL4J RNN convention (b, nIn, t); masks are
(b, t) with 1 = valid.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import operator
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import BaseLayer, register_layer
from deeplearning4j_tpu.nn.weights import init_weight

__all__ = ["SelfAttentionLayer", "LearnedSelfAttentionLayer",
           "RecurrentAttentionLayer", "KerasMultiHeadAttention",
           "paged_attention", "paged_attention_read",
           "paged_kernel_lowerings",
           "paged_kernel_kv_passes", "lowered_for_one_tpu",
           "sparse_in_place_lowerings",
           "paged_latent_attention", "paged_sparse_attention",
           "paged_rows_write", "CacheSpec"]


def _mha(x_btn, Wq, Wk, Wv, Wo, nHeads, mask=None, q_btn=None, impl="auto",
         causal=False):
    """Multi-head attention core.  x_btn: (b, t, n); mask: (b, t_k).

    The score/softmax/context chain dispatches through
    ``parallel.ring.dot_product_attention``: dense (fused by XLA) for short
    sequences, the Pallas flash kernel on TPU for long ones.
    """
    from deeplearning4j_tpu.parallel.ring import dot_product_attention
    q_btn = x_btn if q_btn is None else q_btn
    b, tq, _ = q_btn.shape

    def heads(inp, w):
        y = jnp.matmul(inp, w)                       # (b, t, h*dh)
        return y.reshape(b, inp.shape[1], nHeads, -1).transpose(0, 2, 1, 3)

    qh, kh, vh = heads(q_btn, Wq), heads(x_btn, Wk), heads(x_btn, Wv)
    ctx = dot_product_attention(qh, kh, vh, mask=mask, causal=causal,
                                impl=impl)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, tq, -1)
    return jnp.matmul(ctx, Wo)                       # (b, tq, nOut)


def paged_attention(qh, kh_new, vh_new, poolK, poolV, li, pageTable, pos,
                    start):
    """Causal attention of ``tq`` new positions against a PAGED KV pool.

    The continuous-batching scheduler (``remote/scheduler.py``) decodes
    through this: K/V live in a shared pool of fixed-size pages and each
    decode SLOT addresses its own pages through a page table, so
    sequences of wildly different lengths share one preallocated buffer
    and admitting/retiring a sequence is a host-side page-table edit —
    never a reallocation, and never a new executable shape.

    - ``qh``/``kh_new``/``vh_new``: (slots, heads, tq, headSize) for the
      new positions only (``qh`` may bring ``nRep`` query heads for each
      of the ``heads`` that are stored: :func:`paged_attention_read`);
    - ``poolK``/``poolV``: (nLayers, numPages, pageSize, heads*headSize)
      — the STACKED pools of every layer, token-major: one row per
      position holds all heads side by side, so the two minor
      dimensions are what the TPU tiles as they stand (page 0 is the
      scratch page inactive slots write into);
    - ``li``: the layer whose pages this call reads and writes.  The
      stacked pool is indexed in place; no layer is sliced out and put
      back, so nothing the size of a pool is ever copied;
    - ``pageTable``: (slots, maxPagesPerSeq) int32 physical page ids in
      logical order (unallocated tail entries point at the scratch
      page and are masked out by ``pos``);
    - ``pos``/``start``: (slots,) int32 — per slot, the next write
      index (tokens cached so far) and the index of the first VALID key
      (a left-padded prompt's pad rows lie before it).

    Writes the new K/V rows into their pages (``tq`` may span a page
    boundary — each token's page/offset is computed independently) and
    reads them back through :func:`paged_attention_read`, scores scaled
    by ``headSize ** -0.5``.  Returns ``(ctx, newPoolK, newPoolV)``.
    """
    S, h, tq, d = kh_new.shape
    pageSize = poolK.shape[2]
    wpos = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(pageTable, wpos // pageSize, axis=1)
    off = wpos % pageSize                                    # (S, tq)

    def rows(new, pool):                    # (S, h, tq, d) -> (S, tq, h*d)
        return new.transpose(0, 2, 1, 3).reshape(S, tq, h * d).astype(
            pool.dtype)
    poolK = poolK.at[li, phys, off].set(rows(kh_new, poolK))
    poolV = poolV.at[li, phys, off].set(rows(vh_new, poolV))
    return (paged_attention_read(qh, poolK, poolV, li, pageTable, pos, start),
            poolK, poolV)


def paged_attention_read(qh, poolK, poolV, li, pageTable, pos, start, *,
                         scale=None):
    """:func:`paged_attention` without the write: what a layer calls
    that reads rows another layer wrote, or that has written its own.

    Query ``i`` of slot ``s`` sees key index ``j`` iff ``start[s] <= j <=
    pos[s] + i`` — causal, and blind to the pad, the unwritten tail and
    the scratch page.  ``qh`` may bring GROUPED queries: ``(slots,
    kvHeads * nRep, tq, headSize)`` against a stored row of ``kvHeads *
    headSize`` lanes, query head ``a`` reading KV head ``a // nRep``;
    ``nRep`` is read from the two shapes and nothing sets it.  Scores are
    multiplied by ``scale`` (``headSize ** -0.5`` where none is given: a
    caller whose ``headSize`` lanes are not its scores' width, as a
    differential pair laid into the halves of one 128-lane group, says
    its own) and the context, ``qh``'s shape and dtype, is taken over all
    ``headSize`` lanes of V.

    How the rows are read is decided where the program is lowered, from
    what it is lowered for: for ONE TPU, a kernel reads the slot's live
    pages where they lie (:func:`_attend_pages`); anywhere else (the
    CPU, a pool split over several devices) every slot's whole capacity
    is gathered and attended under the mask (:func:`_attend_gathered`,
    the reference formulation).  Both give a result that depends on a
    slot's logical content alone: not on which physical pages hold it,
    nor on the other slots."""
    return _attend_p.bind(qh, poolK, poolV, pageTable, pos, start, li=li,
                          scale=None if scale is None else float(scale))


_NEG = -1e30              # a masked score


def _attend_gathered(qh, poolK, poolV, pageTable, pos, start, *, li,
                     scale=None):
    """The reference formulation of :func:`paged_attention_read`: gather
    every slot's pages in logical order ((S, capacity, h*d)), split the
    rows into heads, and run scores, softmax and context over the whole
    capacity under the validity mask.  Grouped queries ride as further
    queries of their KV head (``nRep * tq`` of them, one validity each
    ``tq``)."""
    S, H, tq, d = qh.shape
    h = poolK.shape[3] // d
    nRep = H // h
    cap = pageTable.shape[1] * poolK.shape[2]
    wpos = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    k = poolK[li, pageTable].reshape(S, cap, h, d)
    v = poolV[li, pageTable].reshape(S, cap, h, d)
    kpos = jnp.arange(cap, dtype=jnp.int32)
    valid = (kpos[None, None, :] <= wpos[:, :, None]) & \
        (kpos[None, None, :] >= start[:, None, None])        # (S, tq, cap)
    valid = jnp.tile(valid, (1, nRep, 1))
    s = jnp.einsum("bhqd,bkhd->bhqk", qh.reshape(S, h, nRep * tq, d),
                   k.astype(qh.dtype))
    s = s * (1.0 / jnp.sqrt(jnp.asarray(d, s.dtype)) if scale is None
             else jnp.asarray(scale, s.dtype))
    s = jnp.where(valid[:, None], s, jnp.asarray(_NEG, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bhqd", w, v.astype(qh.dtype)).reshape(
        S, H, tq, d)


# -- the kernel: attention over the live pages, where they lie ---------

#: rows of K (and of V) a place of the kernel's grid works on.  With the
#: dot products on the MXU a place costs what its pages' copies cost, and
#: 256 rows a place cost a row what 128 do: 0.758 against 0.759 ms a call
#: at Olmo-Hybrid's row (3,840 bfloat16 lanes, 36,100 live rows), 18.6
#: against 18.6 us at gpt2_xl's (1,600 float32 lanes, 650 live rows) (my
#: chip run, PR 31); only SambaY's row of 1,280 bfloat16 lanes gains (0.336
#: against 0.378 ms a call over 37,800 live rows, four query heads a KV
#: head; in its step of 18.05 ms the eight calls 2.00 against 2.27: 1.6%)
#: (my chip runs, PR 33).  So 128 stays: a slot's last chunk is half empty
#: on average, and its dead buffers are not copied
_CHUNK_ROWS = 128


@functools.partial(jax.jit, static_argnames=("tq", "pageSize", "C"))
def _work_list(pageTable, pos, start, *, tq, pageSize, C):
    """The chunks of LIVE pages of one step, slot after slot, for the
    places of the kernel's grid: ``ceil((pos + tq) / pageSize)`` pages
    hold a slot's rows, less those that lie wholly in its left pad.  For
    each place: the ``C`` physical pages its buffers hold, its slot, the
    position of its first row, and whether it opens (1) / closes (2) a
    slot's pass.  ``total`` places are live; the arrays have room for
    every slot at full capacity, and the places past ``total`` repeat
    the last live one.  A few small integer ops, the same for every
    layer of a step: a jit of its own, so a step traces them once and XLA
    computes them once."""
    i32 = jnp.int32
    S, P = pageTable.shape
    W = S * -(-P // C)
    n = jnp.minimum((pos + (tq + pageSize - 1)) // pageSize, P).astype(i32)
    p0 = jnp.minimum(start // pageSize, n - 1).astype(i32)   # (S,)
    nch = (n - p0 + (C - 1)) // C
    ends = jnp.cumsum(nch).astype(i32)
    total = ends[-1]
    w = jnp.arange(W, dtype=i32)
    wl = jnp.minimum(w, total - 1)
    slot = jnp.sum(wl[:, None] >= ends[None, :], axis=1).astype(i32)
    chunk = wl - (ends - nch)[slot]
    page = (p0[slot] + chunk * C)[:, None] + jnp.arange(C, dtype=i32)
    phys = jnp.take_along_axis(pageTable[slot], jnp.minimum(page, P - 1),
                               axis=1)                       # (W, C)
    # a buffer whose page is not live keeps the page it held the place
    # before (an unchanged index is not copied again); page 0 before any
    at = jax.lax.cummax(jnp.where(page < n[slot][:, None], w[:, None], -1),
                        axis=0)
    phys = jnp.where(at >= 0, jnp.take_along_axis(
        phys, jnp.maximum(at, 0), axis=0), 0)
    flag = (chunk == 0) * 1 + (chunk == nch[slot] - 1) * 2
    return (phys.reshape(-1).astype(i32), slot, (page[:, 0] * pageSize)
            .astype(i32), flag.astype(i32), total)


def _mxu_parts(dtype) -> int:
    """How many bfloat16 pieces hold every bit of a value of ``dtype``:
    one for each eight bits of its significand (bfloat16 1, float32 3)."""
    return -(-(jnp.finfo(dtype).nmant + 1) // 8)


#: the pieces of a float32 query or softmax weight
_F32_PARTS = _mxu_parts(jnp.float32)


def _bf16_parts(x):
    """``x`` as :func:`_mxu_parts` arrays, each exact in bfloat16, whose
    float32 sum is ``x`` to its last bit: the high, middle and low bits of
    a float32 (a bfloat16 ``x`` is its own one piece).  One MXU pass a
    piece then multiplies by all of ``x``, where one pass over ``x``
    itself would round it to eight bits."""
    n = _mxu_parts(x.dtype)
    if n == 1:
        return [x]
    x, parts = x.astype(jnp.float32), []
    for _ in range(n - 1):
        hi = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536),
            jnp.float32)                        # the top 16 bits, cut off
        parts.append(hi)
        x = x - hi
    return parts + [x]


def _lane_tiles(heads, d):
    """How the kernel cuts a row of ``heads * d`` lanes: tiles of ``g``
    whole heads, the fewest whose ``g * d`` lanes are whole lane tiles of
    128 (one head of 128, two of 64), so that every tile starts on one;
    the last tile of a row may be short.  ``(g, tiles)``, a tile as
    ``(first lane, lanes)``."""
    g = min(heads, math.lcm(d, 128) // d)
    return g, [(lo, min(g * d, heads * d - lo))
               for lo in range(0, heads * d, g * d)]


def _pages_kernel(_li_ref, tbl_ref, slot_ref, j0_ref, flag_ref, pos_ref,
                  start_ref, *refs, C, ps, tq, d, masked=False):
    """One place of the grid: ``C`` pages of K and of V of one slot
    (``k_refs``/``v_refs``, each ``(ps, h*d)``, copied in by the
    pipeline while the place before computes), all ``tq`` queries of that
    slot.  Heads are never split out of the rows: the MXU contracts over
    the lanes of the pages as they lie, a tile of ``g`` whole heads at a
    time, with the queries as the small operand.

    - *scores*: ``Q_t`` (a row for every query, head of the tile, query
      head of that head's group (``rep`` of them: ``q_ref`` holds ``tq *
      rep`` rows, each with one query head of every KV head) and
      bfloat16 piece of the float32 ``q``: that head's slice of ``q`` in
      its own lanes, zeros elsewhere) against the tile's lanes of the
      chunk's rows of K, ``(rows, lanes) x (R, lanes) -> (rows, R)``:
      every head's scores with the key positions on the lanes, the
      pieces' rows summed in float32;
    - *softmax*: online, per query head, across the slot's chunks, in
      float32, for all tiles at once;
    - *context*: the weights' bfloat16 pieces as rows, ``(rows, R) x (R,
      lanes)`` against the tile's lanes of V, the pieces summed, each
      row keeping its own head's lanes at the end.

    Grouped queries are only more rows in a query's block of ``Q_t`` (a
    head's ``rep`` query heads under one another, ``g * rep`` rows a
    piece); with one query head a KV head every line is what it was.

    K and V enter the MXU as they are stored, in as many passes as
    :func:`_mxu_parts` of the pool's dtype says (a bfloat16 pool: one, and
    no float32 copy of a page is ever made); ``q`` and the weights keep
    every float32 bit.

    ``masked`` (:func:`_selected_call` alone): an eighth scalar placed the
    block of ``keep_ref (1, R)`` int32, this place's rows of a mask a
    SELECTOR made, and a row counts only where it is set as well."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if masked:
        _chunk_ref, q_ref, keep_ref, *refs = refs
    else:
        q_ref, *refs = refs
    k_refs, v_refs = refs[:C], refs[C:2 * C]
    o_ref, qt_ref, sp_ref, c_ref, m_ref, l_ref, acc_ref = refs[2 * C:]
    w = pl.program_id(0)
    R = C * ps
    g, tiles = _lane_tiles(q_ref.shape[-1] // d, d)
    rep = q_ref.shape[0] // tq      # query heads a KV head
    B = g * rep                     # rows of a query: (head, its query head)
    G = tq * B                      # rows of one piece
    flag = flag_ref[w]

    def rows(part, i):              # piece ``part`` of query ``i``
        return pl.ds(part * G + i * B, B)

    # row j of a query's block belongs to the tile's head j // rep: its lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (B, g * d), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (B, g * d), 0)
    if rep > 1:
        head = jax.lax.div(head, jnp.int32(rep))
    own = (lane >= head * d) & (lane < head * d + d)

    @pl.when((flag & 1) != 0)
    def _():                        # a slot's pass opens
        m_ref[...] = jnp.full(m_ref.shape, _NEG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        qt_ref[...] = jnp.zeros(qt_ref.shape, f32)
        for i in range(tq):
            for t, (lo, n) in enumerate(tiles):
                # the query's ``rep`` rows, once under each head of the tile
                qi = q_ref[pl.ds(i * rep, rep), lo:lo + n]
                qi = jnp.broadcast_to(qi, (g, n)) if rep == 1 else \
                    jnp.concatenate([qi] * g, axis=0)
                for part, qp in enumerate(_bf16_parts(qi)):
                    qt_ref[t, rows(part, i), 0:n] = jnp.where(
                        own[:, :n], qp, f32(0))

    def chunk(refs_, lo, n):
        """The tile's lanes of the chunk's ``R`` rows, as MXU operands."""
        x = jnp.concatenate([r[:, lo:lo + n] for r in refs_], axis=0)
        return [p.astype(bf16) for p in _bf16_parts(x)]

    for t, (lo, n) in enumerate(tiles):
        qt = qt_ref[t, :, 0:n].astype(bf16)
        sp_ref[t] = functools.reduce(operator.add, (
            jax.lax.dot_general(qt, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)
            for k in chunk(k_refs, lo, n)))

    s = slot_ref[w]
    pos, start = pos_ref[s], start_ref[s]
    j = j0_ref[w] + jax.lax.broadcasted_iota(jnp.int32, (1, 1, R), 2)
    scale = []                      # what each query's sums so far shrink by
    for i in range(tq):
        valid = (j >= start) & (j <= pos + i)
        if masked:
            valid = valid & (keep_ref[...] > 0)
        sc = functools.reduce(operator.add, (
            sp_ref[:, rows(part, i), :] for part in range(_F32_PARTS)))
        sc = jnp.where(valid, sc, f32(_NEG))                 # (T, B, R)
        mOld = m_ref[i]                                      # (T, B, 1)
        mNew = jnp.maximum(mOld, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(sc - mNew), f32(0))
        scale.append(jnp.exp(mOld - mNew))
        l_ref[i] = scale[i] * l_ref[i] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[i] = mNew
        for part, pp in enumerate(_bf16_parts(p)):
            sp_ref[:, rows(part, i), :] = pp

    for t, (lo, n) in enumerate(tiles):
        pt = sp_ref[t].astype(bf16)
        c_ref[t, :, 0:n] = functools.reduce(operator.add, (
            jnp.dot(pt, v, preferred_element_type=f32)
            for v in chunk(v_refs, lo, n)))
    for i in range(tq):
        acc_ref[i] = scale[i] * acc_ref[i] + functools.reduce(operator.add, (
            c_ref[:, rows(part, i), :] for part in range(_F32_PARTS)))

    @pl.when((flag & 2) != 0)
    def _():                        # and closes: each row's own lanes,
        for i in range(tq):         # the heads' blocks of rows laid together
            o = jnp.where(own, acc_ref[i] / l_ref[i], f32(0))
            o = jnp.sum(o, axis=1, keepdims=True) if rep == 1 else \
                functools.reduce(operator.add, (
                    o[:, j * rep:(j + 1) * rep] for j in range(g)))
            for t, (lo, n) in enumerate(tiles):              # (T, rep, g*d)
                o_ref[pl.ds(i * rep, rep), lo:lo + n] = o[t][:, :n].astype(
                    o_ref.dtype)


def _attend_pages(qh, poolK, poolV, pageTable, pos, start, *, li,
                  scale=None, interpret=False):
    """:func:`paged_attention_read` as a Pallas TPU kernel, one call a
    layer: K and V are read from the pool's pages WHERE THEY LIE —
    token-major rows of ``heads*headSize`` lanes, all heads side by side
    — and only the pages that hold live rows of a slot.  Nothing is
    gathered into a capacity-wide copy, no row is re-laid into heads,
    no score is taken over a dead position.  The queries go in as ``tq *
    nRep`` rows a slot as wide as a stored row: row ``i * nRep + r``
    holds, in each KV head's lanes, query ``i`` of that head's ``r``-th
    query head.  ``interpret`` is for tests (the CPU)."""
    S, H, tq, d = qh.shape
    ps, hd = poolK.shape[2:]
    h = hd // d
    nRep = H // h
    i32 = jnp.int32
    q = (qh * jnp.asarray(d ** -0.5 if scale is None else scale, qh.dtype)
         ).reshape(S, h, nRep, tq, d).transpose(0, 3, 2, 1, 4).reshape(
             S, tq * nRep, hd).astype(jnp.float32)
    pos, start = pos.astype(i32), start.astype(i32)
    work = _work_list(pageTable.astype(i32), pos, start, tq=tq, pageSize=ps,
                      C=max(1, min(_CHUNK_ROWS // ps, pageTable.shape[1])))
    out = _pages_call(jnp.full((1,), li, i32), *work, pos, start, q, poolK,
                      poolV, headSize=d, tq=tq, interpret=interpret)
    return out.reshape(S, tq, nRep, h, d).transpose(0, 3, 2, 1, 4).reshape(
        S, H, tq, d).astype(qh.dtype)


@functools.partial(jax.jit, static_argnames=("headSize", "tq", "interpret"))
def _pages_call(li, tbl, slot, j0, flag, total, pos, start, q, poolK, poolV,
                *, headSize, tq, interpret):
    """The kernel's call.  The stacked pool goes in whole (the layer is
    an index, so no layer is sliced out), once for each of a chunk's
    ``2 C`` page buffers: each buffer's block is one ``(pageSize, h*d)``
    page, named for every place of the grid by the scalar-prefetched
    work list, and copied in by the kernel's pipeline while the place
    before computes (a page of 1,600 lanes is no multiple of the 128-lane
    tile: the pipeline copies it whole, a hand-written copy of a slice
    of such an array does not compile).  The grid has as many places as
    the step has chunks of live pages.  A jit of its own with the layer
    as an argument: every layer of a step is then the same computation,
    traced and lowered to Mosaic once a program and not once a layer."""
    return _pages_grid((li, tbl, slot, j0, flag, pos, start), total, q, None,
                       poolK, poolV, headSize=headSize, tq=tq,
                       interpret=interpret)


def _pages_grid(scalars, total, q, keep, poolK, poolV, *, headSize, tq,
                interpret):
    """:func:`_pages_call`'s ``pallas_call``, and :func:`_selected_call`'s:
    with ``keep (S, chunks a slot, 1, R)`` the kernel is the masked one
    under a name of its own, ``keep``'s block placed by the last of
    ``scalars`` (the place's chunk of its slot)."""
    tbl, slot = scalars[1], scalars[2]
    S, nq, hd = q.shape             # nq = tq x the query heads a KV head
    ps = poolK.shape[2]
    C = tbl.shape[0] // slot.shape[0]
    d = headSize
    g, tiles = _lane_tiles(hd // d, d)
    T, R = len(tiles), C * ps
    B = nq // tq * g                # rows of one query in a tile's block
    # rows of a tile's block: a query's heads of the tile with their query
    # heads, piece by piece (whole bfloat16 sublane tiles of 16)
    rp = -(-_F32_PARTS * tq * B // 16) * 16
    f32 = jnp.float32
    masked = keep is not None

    # index maps: ``w * 0`` and not ``0`` (the package enables x64, and a
    # bare literal would be an int64 Mosaic has not)
    def page_spec(c):
        return pl.BlockSpec(
            (None, None, ps, hd),
            lambda w, li, tbl, *_: (li[0], tbl[w * C + c], w * 0, w * 0))
    row_spec = pl.BlockSpec(
        (None, nq, hd), lambda w, li, tbl, slot, *_: (slot[w], w * 0, w * 0))
    keep_spec = pl.BlockSpec(
        (None, None, 1, R),
        lambda w, li, tbl, slot, *s: (slot[w], s[-1][w], w * 0, w * 0))
    return pl.pallas_call(
        functools.partial(_pages_kernel, C=C, ps=ps, tq=tq, d=d,
                          masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(total,),
            in_specs=[row_spec] + [keep_spec] * masked
            + [page_spec(c) for c in range(C)] * 2,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((T, rp, g * d), f32),     # the queries' Q_t
                pltpu.VMEM((T, rp, R), f32),         # scores, then weights
                pltpu.VMEM((T, rp, g * d), f32),     # the chunk's context
                pltpu.VMEM((tq, T, B, 1), f32),      # running max
                pltpu.VMEM((tq, T, B, 1), f32),      # running sum
                pltpu.VMEM((tq, T, B, g * d), f32),  # context
            ]),
        out_shape=jax.ShapeDtypeStruct((S, nq, hd), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="paged_selected_attention" if masked else "paged_attention",
        interpret=interpret,
    )(*scalars, q, *([keep] * masked), *([poolK] * C), *([poolV] * C))


#: how often the step's read was lowered as the kernel, and the MXU passes
#: over a lane tile of K (and of V) of the latest such lowering (program
#: telemetry: the batcher's gauges read both around its warm-up)
_kernelLowerings = [0, 0]


def paged_kernel_lowerings() -> int:
    """How many times :func:`paged_attention`'s read has been lowered as
    the TPU kernel in this process (once for each layer READ by a program
    built for one TPU, however many of its layers read that one; never on
    the CPU or for a pool split over devices)."""
    return _kernelLowerings[0]


def paged_kernel_kv_passes() -> int:
    """MXU passes over one lane tile of K (and one of V) a chunk, for all
    queries of the slot, in the kernel as it was last lowered: what the
    pool's dtype needs to enter the MXU whole (:func:`_mxu_parts`: a
    bfloat16 pool 1, a float32 pool 3); 0 before any kernel lowering."""
    return _kernelLowerings[1]


def lowered_for_one_tpu(ctx) -> bool:
    """Whether the program being lowered is built for ONE TPU: what a
    primitive with a Mosaic kernel and a ``jax.numpy`` form chooses
    between them by (no knob).  Not the CPU, and not several devices,
    which a Mosaic kernel cannot be partitioned over."""
    mc = ctx.module_context
    return tuple(mc.platforms) == ("tpu",) and \
        getattr(mc.axis_context, "num_devices", None) == 1


def _lowered_as_kernel(ctx, poolDtype) -> bool:
    """Choose by what the program is lowered for, not by a knob: one TPU
    -> the kernel; the CPU, or several devices (a pool whose lanes are
    split over a mesh) -> the gathered reference.  Counts a kernel
    lowering."""
    kernel = lowered_for_one_tpu(ctx)
    if kernel:
        _kernelLowerings[0] += 1
        _kernelLowerings[1] = _mxu_parts(poolDtype)
    return kernel


def _attend_lowering(ctx, *args, li, scale):
    kernel = _lowered_as_kernel(ctx, ctx.avals_in[1].dtype)
    return mlir.lower_fun(
        functools.partial(_attend_pages if kernel else _attend_gathered,
                          li=li, scale=scale),
        multiple_results=False)(ctx, *args)


_attend_p = jex_core.Primitive("paged_attend")


@functools.partial(jax.jit, static_argnames=("li", "scale"))
def _attend_eager(*args, li, scale):
    """Outside any jit the primitive runs as a program of its own."""
    return _attend_p.bind(*args, li=li, scale=scale)


_attend_p.def_impl(_attend_eager)
_attend_p.def_abstract_eval(
    lambda qh, *_, li, scale: jax.core.ShapedArray(qh.shape, qh.dtype))
mlir.register_lowering(_attend_p, _attend_lowering)


# -- the latent form: one row a position, key and value at once --------

def paged_latent_attention(qh, rowNew, pool, li, pageTable, pos, start, *,
                           valueWidth, scale):
    """:func:`paged_attention` where a position keeps ONE row that all
    query heads read, as key and as value (``CacheSpec.latentWidth``):
    the cache of multi-head latent attention in its absorbed form.

    - ``qh`` (slots, heads, tq, W): each head's query in the row's own
      ``W`` lanes (its up-projection folded into it, the rotated part
      behind, zeros in the row's padding);
    - ``rowNew`` (slots, tq, W): the new positions' rows;
    - ``pool`` (nLayers, numPages, pageSize, W): the one stacked pool;
    - ``li``, ``pageTable``, ``pos``, ``start`` as in
      :func:`paged_attention`; ``scale`` multiplies the scores.

    Scores are taken over all ``W`` lanes, the context over the first
    ``valueWidth``: ``(ctx (slots, heads, tq, valueWidth) float32,
    newPool)``.  Queries and softmax weights enter the matmuls in the
    pool's dtype, as the rows do; the softmax itself is float32.  Lowered
    like :func:`paged_attention`: for one TPU the kernel
    (:func:`_attend_latent_pages`) over the same work list of live
    chunks, one page copy serving keys and values; elsewhere the
    gathered reference (:func:`_attend_latent_gathered`)."""
    S, h, tq, W = qh.shape
    pageSize = pool.shape[2]
    wpos = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    phys = jnp.take_along_axis(pageTable, wpos // pageSize, axis=1)
    pool = pool.at[li, phys, wpos % pageSize].set(rowNew.astype(pool.dtype))
    q = (qh.astype(jnp.float32) * jnp.float32(scale)).astype(pool.dtype)
    ctx = _attend_latent_p.bind(q, pool, pageTable, pos, start, li=li,
                                valueWidth=int(valueWidth))
    return ctx, pool


def _attend_latent_gathered(q, pool, pageTable, pos, start, *, li,
                            valueWidth):
    """The reference formulation of the latent read: every slot's pages
    gathered in logical order ((S, capacity, W)), scores, softmax and
    context over the whole capacity under the validity mask."""
    S, h, tq, W = q.shape
    f32 = jnp.float32
    cap = pageTable.shape[1] * pool.shape[2]
    wpos = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    rows = pool[li, pageTable].reshape(S, cap, W).astype(f32)
    kpos = jnp.arange(cap, dtype=jnp.int32)
    valid = (kpos[None, None, :] <= wpos[:, :, None]) & \
        (kpos[None, None, :] >= start[:, None, None])        # (S, tq, cap)
    s = jnp.einsum("bhqw,bkw->bhqk", q.astype(f32), rows)
    w = jax.nn.softmax(jnp.where(valid[:, None], s, f32(_NEG)), axis=-1)
    return jnp.einsum("bhqk,bkv->bhqv", w.astype(pool.dtype).astype(f32),
                      rows[..., :valueWidth])


#: rows of the latent pool a place of the grid works on (see
#: :data:`_CHUNK_ROWS`).  A latent row is a sixth of Olmo-Hybrid's K and V
#: together, so a chunk of 128 would be 0.16 MB, a fifth of a microsecond
#: of copies under a place's own third of one
_LATENT_CHUNK_ROWS = 512


def _mxu_dot(a, b, dims):
    """``dot_general(a, b)`` on the MXU with every bit of both operands:
    one bfloat16 pass for each pair of their :func:`_bf16_parts` (one
    pass for bfloat16 operands, nine for float32), summed in float32."""
    bf16 = jnp.bfloat16
    return functools.reduce(operator.add, (
        jax.lax.dot_general(x.astype(bf16), y.astype(bf16), dims,
                            preferred_element_type=jnp.float32)
        for x in _bf16_parts(a) for y in _bf16_parts(b)))


def _latent_kernel(_li_ref, tbl_ref, slot_ref, j0_ref, flag_ref, pos_ref,
                   start_ref, q_ref, *refs, C, ps, tq, vw):
    """One place of the grid: ``C`` pages of one slot's latent rows
    (``r_refs``, each ``(ps, W)``, copied in by the pipeline while the
    place before computes) and all heads of that slot's ``tq`` queries
    as the ROWS of one operand (``q_ref (tq * heads, W)``): every head
    reads the same rows, so a chunk's scores are one matmul ``(tq *
    heads, W) x (R, W)^T`` and its context one ``(tq * heads, R) x (R,
    vw)`` over the first ``vw`` lanes of the same copy.  Softmax online
    across the slot's chunks, in float32."""
    f32 = jnp.float32
    r_refs = refs[:C]
    o_ref, m_ref, l_ref, acc_ref = refs[C:]
    w = pl.program_id(0)
    R = C * ps
    N = q_ref.shape[0]
    flag = flag_ref[w]

    @pl.when((flag & 1) != 0)
    def _():                        # a slot's pass opens
        m_ref[...] = jnp.full(m_ref.shape, _NEG, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    rows = jnp.concatenate([r[...] for r in r_refs], axis=0)     # (R, W)
    sc = _mxu_dot(q_ref[...], rows, (((1,), (1,)), ((), ())))    # (N, R)
    s = slot_ref[w]
    j = j0_ref[w] + jax.lax.broadcasted_iota(jnp.int32, (N, R), 1)
    last = pos_ref[s]
    if tq > 1:                      # row n is query n // heads
        last = last + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (N, R), 0),
            jnp.int32(N // tq))
    valid = (j >= start_ref[s]) & (j <= last)
    sc = jnp.where(valid, sc, f32(_NEG))
    mOld = m_ref[...]                                            # (N, 1)
    mNew = jnp.maximum(mOld, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(sc - mNew), f32(0))
    shrink = jnp.exp(mOld - mNew)
    l_ref[...] = shrink * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = mNew
    acc_ref[...] = shrink * acc_ref[...] + _mxu_dot(
        p.astype(rows.dtype), rows[:, 0:vw], (((1,), (0,)), ((), ())))

    @pl.when((flag & 2) != 0)
    def _():                        # and closes
        o_ref[...] = acc_ref[...] / l_ref[...]


def _attend_latent_pages(q, pool, pageTable, pos, start, *, li, valueWidth,
                         interpret=False):
    """The latent read as a Pallas TPU kernel, one call a layer, over
    :func:`_work_list`'s live chunks: only the pages that hold live rows
    of a slot are read, each once, for keys and values.  ``interpret`` is
    for tests (the CPU)."""
    S, h, tq, W = q.shape
    ps = pool.shape[2]
    i32 = jnp.int32
    pos, start = pos.astype(i32), start.astype(i32)
    work = _work_list(
        pageTable.astype(i32), pos, start, tq=tq, pageSize=ps,
        C=max(1, min(_LATENT_CHUNK_ROWS // ps, pageTable.shape[1])))
    out = _latent_call(
        jnp.full((1,), li, i32), *work, pos, start,
        q.transpose(0, 2, 1, 3).reshape(S, tq * h, W), pool,
        valueWidth=valueWidth, tq=tq, interpret=interpret)
    return out.reshape(S, tq, h, valueWidth).transpose(0, 2, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("valueWidth", "tq", "interpret"))
def _latent_call(li, tbl, slot, j0, flag, total, pos, start, q, pool, *,
                 valueWidth, tq, interpret):
    """The latent kernel's call: :func:`_pages_call`'s grid, work list
    and page blocks, with one pool and one set of ``C`` page buffers."""
    S, N, W = q.shape
    ps = pool.shape[2]
    C = tbl.shape[0] // slot.shape[0]
    f32 = jnp.float32

    def page_spec(c):
        return pl.BlockSpec(
            (None, None, ps, W),
            lambda w, li, tbl, *_: (li[0], tbl[w * C + c], w * 0, w * 0))

    def row_spec(width):
        return pl.BlockSpec(
            (None, N, width),
            lambda w, li, tbl, slot, *_: (slot[w], w * 0, w * 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, C=C, ps=ps, tq=tq, vw=valueWidth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(total,),
            in_specs=[row_spec(W)] + [page_spec(c) for c in range(C)],
            out_specs=row_spec(valueWidth),
            scratch_shapes=[
                pltpu.VMEM((N, 1), f32),             # running max
                pltpu.VMEM((N, 1), f32),             # running sum
                pltpu.VMEM((N, valueWidth), f32),    # context
            ]),
        out_shape=jax.ShapeDtypeStruct((S, N, valueWidth), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="paged_latent_attention",
        interpret=interpret,
    )(li, tbl, slot, j0, flag, pos, start, q, *([pool] * C))


def _attend_latent_lowering(ctx, *args, li, valueWidth):
    kernel = _lowered_as_kernel(ctx, ctx.avals_in[1].dtype)
    return mlir.lower_fun(
        functools.partial(
            _attend_latent_pages if kernel else _attend_latent_gathered,
            li=li, valueWidth=valueWidth),
        multiple_results=False)(ctx, *args)


_attend_latent_p = jex_core.Primitive("paged_attend_latent")


@functools.partial(jax.jit, static_argnames=("li", "valueWidth"))
def _attend_latent_eager(*args, li, valueWidth):
    return _attend_latent_p.bind(*args, li=li, valueWidth=valueWidth)


_attend_latent_p.def_impl(_attend_latent_eager)
_attend_latent_p.def_abstract_eval(
    lambda q, *_, li, valueWidth: jax.core.ShapedArray(
        q.shape[:-1] + (valueWidth,), jnp.float32))
mlir.register_lowering(_attend_latent_p, _attend_latent_lowering)


# -- the sparse form: index rows choose which K/V rows a query reads ----

def paged_sparse_attention(qh, kNew, vNew, qI, wI, kINew, poolK, poolV,
                           poolI, li, pageTable, pos, start, *, topk):
    """:func:`paged_attention` where a learned SELECTOR decides which of a
    slot's live rows the query reads (DeepSeek-V3.2-Exp's lightning
    indexer): beside its K and V rows a position keeps an INDEX ROW, one
    key of ``CacheSpec.indexWidth`` lanes for all index heads, in a third
    pool that the same page table addresses.

    - ``qh`` (slots, kvHeads * nRep, 1, headSize), ``kNew`` / ``vNew``
      (slots, kvHeads, 1, headSize): as in :func:`paged_attention`, one
      new position a slot;
    - ``qI`` (slots, indexHeads, indexWidth) float32, ``wI`` (slots,
      indexHeads) float32: the new position's index queries and their
      weights; ``kINew`` (slots, indexWidth): its index key;
    - ``poolI`` (nLayers, numPages, pageSize, W): the stacked index rows,
      ``W`` whole lane tiles (zeros behind the ``indexWidth`` lanes).

    Writes the three new rows, scores every live row ``s`` of a slot,
    ``I_s = sum_j wI_j ReLU(qI_j . kI_s)`` in float32, takes the ``topk``
    largest (every live row while there are no more than ``topk``; of
    equal scores the earlier position) and attends over those rows of K
    and V alone, read through the page table: ``(ctx (slots, heads, 1,
    headSize) float32, poolK, poolV, poolI)``.  The result depends on a
    slot's logical content alone.  Lowered like :func:`paged_attention`,
    from what the program is lowered for and its static shapes, in one of
    three forms:

    - one TPU, a slot's capacity x a row's bytes up to
      :data:`_IN_PLACE_BYTES_A_PICK` a chosen row
      (:func:`_attend_sparse_in_place`): a kernel scores the live index
      pages where they lie (:func:`_index_pages`), the selection is a MASK
      (:func:`_select_mask`, by bisection: no sort), and the chosen rows
      are attended IN THE POOL by a masked pass of the paged-attention
      kernel over the slot's live pages (:func:`_attend_selected_pages`):
      no index of a chosen row is formed, no row leaves the pool;
    - one TPU, a larger capacity (:func:`_attend_sparse_pages`): the same
      scoring kernel, then ``lax.top_k`` and a row gather of the chosen K
      and V rows through the page table (:func:`_select_attend`), whose
      cost does not grow with what is live;
    - elsewhere (the CPU, several devices; :func:`_attend_sparse_gathered`,
      the reference formulation): every slot's whole capacity of index
      rows is gathered (:func:`_index_gathered`), then
      :func:`_select_attend`."""
    S, h, tq, d = kNew.shape
    if tq != 1:
        raise ValueError("the sparse read takes one new position a slot")
    ps = poolK.shape[2]

    def row(new, pool):
        a = new.reshape(S, -1)
        return jnp.pad(a, ((0, 0), (0, pool.shape[3] - a.shape[1]))
                       ).astype(pool.dtype)
    # every operation of the read carries its name in the compiled
    # program's metadata (``op_name``), the XLA ones between the kernels
    # too: a device trace's ops are told apart by it
    with jax.named_scope("paged_sparse_attention"):
        phys = jnp.take_along_axis(pageTable, (pos // ps)[:, None],
                                   axis=1)[:, 0]
        off = pos % ps
        poolK = poolK.at[li, phys, off].set(row(kNew, poolK))
        poolV = poolV.at[li, phys, off].set(row(vNew, poolV))
        poolI = poolI.at[li, phys, off].set(row(kINew, poolI))
        ctx = _attend_sparse_p.bind(
            qh.astype(poolK.dtype), qI.astype(jnp.float32),
            wI.astype(jnp.float32), poolK, poolV, poolI, pageTable, pos,
            start, li=li, topk=int(topk))
    return ctx, poolK, poolV, poolI


def _index_gathered(qI, wI, poolI, pageTable, *, li):
    """The reference formulation of the index scores: every slot's index
    rows gathered in logical order, ``scores (S, capacity)`` float32 from
    position 0 on."""
    S, _, dI = qI.shape
    f32 = jnp.float32
    cap = pageTable.shape[1] * poolI.shape[2]
    rows = poolI[li, pageTable].reshape(S, cap, -1)[..., :dI].astype(f32)
    s = jnp.einsum("sjd,scd->sjc", qI, rows,
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("sj,sjc->sc", wI, jnp.maximum(s, f32(0)),
                      precision=jax.lax.Precision.HIGHEST)


_INT_MIN = -2 ** 31


def _order_key(x):
    """float32 -> int32 that orders as the floats do (``-0.0`` taken as
    ``0.0``): the bit pattern, its magnitude bits flipped below zero."""
    i32 = jnp.int32
    b = jax.lax.bitcast_convert_type(x + jnp.float32(0), i32)
    return jnp.where(b < 0, b ^ i32(0x7FFFFFFF), b)


def _kth_largest(key, k: int):
    """``key (..., n)`` int32 -> the largest ``th (..., 1)`` such that at
    least ``k`` keys are ``>= th`` (``INT_MIN`` where fewer than ``k`` keys
    lie above it): bisection over the 32 bits, the sign first."""
    i32 = jnp.int32
    count = lambda th: jnp.sum(key >= th, axis=-1, keepdims=True, dtype=i32)
    th = jnp.where(count(i32(0)) >= k, i32(0), i32(_INT_MIN))
    th = jnp.broadcast_to(th, key.shape[:-1] + (1,))

    def bit(i, th):
        cand = th + jnp.left_shift(i32(1), i32(30) - i.astype(i32))
        return jnp.where(count(cand) >= k, cand, th)
    return jax.lax.fori_loop(0, 31, bit, th)


def _select_mask(scores, valid, k: int):
    """``scores (..., n)`` float32, ``valid (..., n)`` -> bool: the ``k``
    valid positions of largest score (all of them where there are no more
    than ``k``); of equal scores the earlier position.  What a stable
    descending sort would take, found without one: the ``k``-th largest
    score exactly, everything above it, and of the ties the first few."""
    i32 = jnp.int32
    key = jnp.where(valid, _order_key(scores), i32(_INT_MIN))
    th = _kth_largest(key, k)
    above = key > th
    tie = (key == th) & (th > i32(_INT_MIN))
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=i32)
    return above | (tie & (jnp.cumsum(tie, axis=-1, dtype=i32) <= need))


def _live_columns(n, first, pos, start):
    """``(S, n)`` bool over a slot's scores: column ``c`` of slot ``s`` is
    the position ``first[s] + c``, live while ``start <= . <= pos``."""
    j = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    return (j >= start[:, None]) & (j <= pos[:, None])


def _select_attend(q, scores, first, poolK, poolV, pageTable, pos, start, *,
                   li, topk):
    """The chosen rows read as a COPY, what the reference formulation and
    the one-TPU form beyond the crossover share (under it the rows are
    read where they lie: :func:`_attend_selected_pages`): ``scores (S,
    n)`` of the positions ``first[s] + arange(n)`` -> the ``topk`` best
    live ones (``lax.top_k`` gives equal scores to the lower index, the
    earlier position), their K and V rows gathered through the page
    table, and softmax attention over them.  Queries and softmax weights
    enter the matmuls rounded to the pool's dtype, as the rows are."""
    S, H, _, d = q.shape
    f32, i32 = jnp.float32, jnp.int32
    ps = poolK.shape[2]
    h = poolK.shape[3] // d
    n = scores.shape[1]
    live = _live_columns(n, first, pos, start)
    top, at = jax.lax.top_k(jnp.where(live, scores, f32(-jnp.inf)),
                            min(topk, n))
    keep = top > f32(-jnp.inf)                               # (S, k)
    idx = jnp.minimum(first[:, None] + at.astype(i32),
                      pageTable.shape[1] * ps - 1)
    phys = jnp.take_along_axis(pageTable, idx // ps, axis=1)
    k = poolK[li, phys, idx % ps].reshape(S, -1, h, d).astype(f32)
    v = poolV[li, phys, idx % ps].reshape(S, -1, h, d).astype(f32)
    sc = jnp.einsum("sgrd,skgd->sgrk", q.reshape(S, h, H // h, d
                                                 ).astype(f32), k,
                    precision=jax.lax.Precision.HIGHEST) * f32(d ** -0.5)
    w = jax.nn.softmax(jnp.where(keep[:, None, None], sc, f32(_NEG)),
                       axis=-1)
    ctx = jnp.einsum("sgrk,skgd->sgrd", w.astype(poolV.dtype).astype(f32),
                     v, precision=jax.lax.Precision.HIGHEST)
    return ctx.reshape(S, H, 1, d)


def _attend_sparse_gathered(q, qI, wI, poolK, poolV, poolI, pageTable, pos,
                            start, *, li, topk):
    scores = _index_gathered(qI, wI, poolI, pageTable, li=li)
    return _select_attend(q, scores, jnp.zeros_like(pos), poolK, poolV,
                          pageTable, pos, start, li=li, topk=topk)


#: index rows a place of the scoring kernel's grid works on: a row is 128
#: lanes, a sixth of a latent row (see :data:`_LATENT_CHUNK_ROWS`)
_INDEX_CHUNK_ROWS = 512


def _index_kernel(_li_ref, tbl_ref, slot_ref, chunk_ref, q_ref, w_ref,
                  *refs, C):
    """One place of the grid: ``C`` pages of one slot's index rows against
    that slot's index queries (``q_ref (heads, W)`` float32, whole in
    three bfloat16 pieces) -> the rows' scores ``(1, R)``: ReLU a head,
    weighted by ``w_ref (heads, 128)`` (a head's weight in every lane),
    summed over the heads."""
    del tbl_ref, slot_ref, chunk_ref
    r_refs, o_ref = refs[:C], refs[C]
    rows = jnp.concatenate([r[...] for r in r_refs], axis=0)     # (R, W)
    sc = _mxu_dot(q_ref[...], rows, (((1,), (1,)), ((), ())))    # (hI, R)
    o_ref[...] = jnp.sum(jnp.maximum(sc, jnp.float32(0)) * w_ref[:, 0:1],
                         axis=0, keepdims=True)


def _index_pages(qI, wI, poolI, pageTable, pos, start, *, li,
                 interpret=False):
    """The index scores as a Pallas TPU kernel over :func:`_work_list`'s
    live chunks: only the pages that hold live rows of a slot are read,
    each once.  ``(scores (S, n) float32, first position (S,))``: a slot's
    scores begin at its first live page; what lies outside its live rows
    is whatever the buffer held (the caller masks by position).
    ``interpret`` is for tests (the CPU)."""
    S, hI, dI = qI.shape
    ps, W = poolI.shape[2], poolI.shape[3]
    i32, f32 = jnp.int32, jnp.float32
    P = pageTable.shape[1]
    C = max(1, min(_INDEX_CHUNK_ROWS // ps, P))
    pos, start = pos.astype(i32), start.astype(i32)
    tbl, slot, j0, _flag, total = _work_list(
        pageTable.astype(i32), pos, start, tq=1, pageSize=ps, C=C)
    # as _work_list places a slot's first chunk: on its first live page
    n = jnp.minimum((pos + ps) // ps, P).astype(i32)
    first = jnp.minimum(start // ps, n - 1).astype(i32) * ps
    chunk = (j0 - first[slot]) // (C * ps)
    out = _index_call(
        jnp.full((1,), li, i32), tbl, slot, chunk.astype(i32), total,
        jnp.pad(qI, ((0, 0), (0, 0), (0, W - dI))),
        jnp.broadcast_to(wI[:, :, None], (S, hI, 128)).astype(f32), poolI,
        interpret=interpret)
    return out.reshape(S, -1), first


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_call(li, tbl, slot, chunk, total, q, w, pool, *, interpret):
    """The scoring kernel's call: :func:`_latent_call`'s grid and page
    blocks; a place writes its chunk's scores where the slot's row of the
    output keeps that chunk."""
    S, hI, W = q.shape
    ps = pool.shape[2]
    C = tbl.shape[0] // slot.shape[0]
    NC = slot.shape[0] // S                  # chunks a slot can have

    def page_spec(c):
        return pl.BlockSpec(
            (None, None, ps, W),
            lambda w, li, tbl, *_: (li[0], tbl[w * C + c], w * 0, w * 0))

    def slot_spec(width):
        return pl.BlockSpec(
            (None, hI, width),
            lambda w, li, tbl, slot, *_: (slot[w], w * 0, w * 0))
    return pl.pallas_call(
        functools.partial(_index_kernel, C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(total,),
            in_specs=[slot_spec(W), slot_spec(128)]
            + [page_spec(c) for c in range(C)],
            out_specs=pl.BlockSpec(
                (None, None, 1, C * ps),
                lambda w, li, tbl, slot, chunk: (slot[w], chunk[w], w * 0,
                                                 w * 0))),
        out_shape=jax.ShapeDtypeStruct((S, NC, 1, C * ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="paged_sparse_attention_index",
        interpret=interpret,
    )(li, tbl, slot, chunk, q, w, *([pool] * C))


def _attend_sparse_pages(q, qI, wI, poolK, poolV, poolI, pageTable, pos,
                         start, *, li, topk, interpret=False):
    """The one-TPU form beyond :data:`_IN_PLACE_BYTES_A_PICK`: the scoring
    kernel, then :func:`_select_attend`'s sort and row gather."""
    scores, first = _index_pages(qI, wI, poolI, pageTable, pos, start,
                                 li=li, interpret=interpret)
    return _select_attend(q, scores, first, poolK, poolV, pageTable, pos,
                          start, li=li, topk=topk)


#: bytes of K (and as many of V) a place of the MASKED pass's grid moves, as
#: whole pages: 512 rows of Keye-VL's 1 KB row, 1 MB a place.  At
#: :data:`_CHUNK_ROWS` a place of that pool would move 0.31 us of bytes
#: under a place's own 0.3-0.4 us.  One layer's pass alone over 16 slots
#: of 18,500 / 32,768 live rows (0.740 / 1.311 ms of bytes at 819 GB/s), ms
#: a call at 256 / 512 / 1,024 / 2,048 rows a place: 1.132 / 0.968 / 0.956
#: / 0.973 and 1.888 / 1.590 / 1.582 / 1.590; the whole read 1.573 / 1.294
#: / 1.389 / 1.394 and 2.519 / 2.110 / 2.206 / 2.212 (my chip run, PR 41):
#: from 512 rows on the pass costs the same, and at 512 its work list is
#: the scoring kernel's (:data:`_INDEX_CHUNK_ROWS` rows of this page
#: size), built once a step for both
_SELECTED_PLACE_BYTES = 512 << 10

#: the masked pass streams a slot's live pages, the gather fetches ``topk``
#: rows whatever is live and sorts the capacity: in place while a slot's
#: CAPACITY in bytes of K is at most this many a chosen row, 20 x ``topk``
#: rows of 1 KB.  One layer's read with every slot FULL, in place against
#: sort + gather, ms a call at a capacity of 17 / 19 / 20 / 22 x ``topk``
#: = 2,048: 2.200 / 2.505, 2.481 / 2.621, 2.622 / 2.645, 2.867 / 2.766 (at
#: 34 x, 1,024 rows a place: 4.574 / 3.726); half full the pass wins at
#: each (1.235 / 2.277 ... 1.617 / 2.466; at 34 x 2.628 / 3.267) (my chip
#: runs, PR 41).  The choice is static, so it is made for the full pool
_IN_PLACE_BYTES_A_PICK = 20 << 10


def _attend_selected_pages(q, keep, first, poolK, poolV, pageTable, pos,
                           start, *, li, interpret=False):
    """:func:`_attend_pages` over the rows a selector KEPT: ``keep (S, n)``
    bool, column ``c`` of slot ``s`` the position ``first[s] + c`` (as
    :func:`_index_pages` lays its scores: from the slot's first live
    page).  The kernel's pass over the slot's live pages where they lie,
    a row counting only where ``keep`` is set: no index of a kept row is
    formed and no row leaves the pool.  ``q (S, H, 1, d)`` in the pool's
    dtype; the context comes back float32."""
    S, H, _, d = q.shape
    ps, hd = poolK.shape[2:]
    h = hd // d
    i32 = jnp.int32
    P = pageTable.shape[1]
    C = max(1, min(_SELECTED_PLACE_BYTES // (hd * poolK.dtype.itemsize)
                   // ps, P))
    R = C * ps
    pos, start = pos.astype(i32), start.astype(i32)
    tbl, slot, j0, flag, total = _work_list(
        pageTable.astype(i32), pos, start, tq=1, pageSize=ps, C=C)
    width = -(-P // C) * R                   # every chunk a slot can have
    n = min(keep.shape[1], width)
    keep = jnp.pad(keep[:, :n], ((0, 0), (0, width - n))).astype(i32)
    rows = (q.astype(jnp.float32) * jnp.float32(d ** -0.5)).reshape(
        S, h, H // h, d).transpose(0, 2, 1, 3).reshape(S, H // h, hd)
    out = _selected_call(
        jnp.full((1,), li, i32), tbl, slot, j0, flag, total, pos, start,
        ((j0 - first[slot]) // R).astype(i32), rows,
        keep.reshape(S, -1, 1, R), poolK, poolV, headSize=d,
        interpret=interpret)
    return out.reshape(S, H // h, h, d).transpose(0, 2, 1, 3).reshape(
        S, H, 1, d)


@functools.partial(jax.jit, static_argnames=("headSize", "interpret"))
def _selected_call(li, tbl, slot, j0, flag, total, pos, start, chunk, q,
                   keep, poolK, poolV, *, headSize, interpret):
    """:func:`_pages_call` under a mask: ``keep (S, chunks a slot, 1, R)``
    int32, a place reading the block of its slot's ``chunk``.  Its device
    op is ``paged_selected_attention*``."""
    return _pages_grid((li, tbl, slot, j0, flag, pos, start, chunk), total,
                       q, keep, poolK, poolV, headSize=headSize, tq=1,
                       interpret=interpret)


def _attend_sparse_in_place(q, qI, wI, poolK, poolV, poolI, pageTable, pos,
                            start, *, li, topk, interpret=False):
    """The one-TPU form up to :data:`_IN_PLACE_BYTES_A_PICK`: the scoring
    kernel, the selection as a MASK (:func:`_select_mask`: what a stable
    descending sort would take, found by bisection) and the masked pass
    of the paged-attention kernel over the slot's live pages
    (:func:`_attend_selected_pages`)."""
    scores, first = _index_pages(qI, wI, poolI, pageTable, pos, start,
                                 li=li, interpret=interpret)
    keep = _select_mask(
        scores, _live_columns(scores.shape[1], first, pos, start), topk)
    return _attend_selected_pages(q, keep, first, poolK, poolV, pageTable,
                                  pos, start, li=li, interpret=interpret)


#: how often the sparse read was lowered as the masked pass
_inPlaceLowerings = [0]


def sparse_in_place_lowerings() -> int:
    """How many times :func:`paged_sparse_attention`'s read has been
    lowered as the masked pass over the live pages in this process (once a
    layer of a program built for one TPU whose capacity is under the
    crossover; never where it gathers the chosen rows)."""
    return _inPlaceLowerings[0]


def _attend_sparse_lowering(ctx, *args, li, topk):
    form = _attend_sparse_gathered
    if _lowered_as_kernel(ctx, ctx.avals_in[5].dtype):
        poolK, pageTable = ctx.avals_in[3], ctx.avals_in[6]
        rowBytes = poolK.shape[3] * poolK.dtype.itemsize
        capacity = pageTable.shape[1] * poolK.shape[2]
        form = _attend_sparse_pages
        if capacity * rowBytes <= _IN_PLACE_BYTES_A_PICK * topk:
            form = _attend_sparse_in_place
            _inPlaceLowerings[0] += 1
    return mlir.lower_fun(functools.partial(form, li=li, topk=topk),
                          multiple_results=False)(ctx, *args)


_attend_sparse_p = jex_core.Primitive("paged_sparse_attention")


@functools.partial(jax.jit, static_argnames=("li", "topk"))
def _attend_sparse_eager(*args, li, topk):
    return _attend_sparse_p.bind(*args, li=li, topk=topk)


_attend_sparse_p.def_impl(_attend_sparse_eager)
_attend_sparse_p.def_abstract_eval(
    lambda q, *_, li, topk: jax.core.ShapedArray(q.shape, jnp.float32))
mlir.register_lowering(_attend_sparse_p, _attend_sparse_lowering)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a served model's layers keep between decode steps — the
    model tells the scheduler's ``KVCachePool`` through its
    ``cacheSpec()``; the pool allocates exactly this and nothing selects
    between layouts.  Three kinds of state:

    - *paged*: ``pagedLayers`` layers own pages that grow with the
      sequence, one row a position, ``rowWidth`` lanes wide (written by
      their layer, readable by others).  A row is one of two things.
      *Keys and values*: ``kvHeads`` heads of ``headSize`` side by side,
      in a K pool and a V pool of the same shape (a GPT-style stack is
      the case "every layer paged").  Or a *latent* row
      (``latentWidth > 0``): ONE pool and no V, a row of ``latentWidth``
      lanes that every query head reads both as the bulk of its key and,
      the same lanes again, as its value, followed by ``ropeWidth`` lanes
      that only the keys have (the one rotated key all heads share); the
      row is stored in whole lane tiles of 128, zeros behind the
      ``latentWidth + ropeWidth`` that mean something
      (:func:`paged_latent_attention`).  A model whose attention
      SELECTS its rows (``indexWidth > 0``) keeps beside K and V a third
      pool of *index rows* of the same pages: one key of ``indexWidth``
      lanes a position a layer, stored as ``indexRowWidth``
      (:func:`paged_sparse_attention`);
    - *ring*: ``ringLayers`` layers keep the last ``ringRows`` K/V rows
      of every slot, written modulo ``ringRows``;
    - *recurrent*: ``slotState`` names fixed-size arrays ``(name,
      (layers, *shape a slot), dtype)``, overwritten every step and whole
      at admission.
    """
    pagedLayers: int
    kvHeads: int
    headSize: int
    dtype: Any = jnp.float32
    ringLayers: int = 0
    ringRows: int = 0
    slotState: Tuple[Tuple[str, Tuple[int, ...], Any], ...] = ()
    latentWidth: int = 0
    ropeWidth: int = 0
    indexWidth: int = 0

    @property
    def indexRowWidth(self) -> int:
        """Lanes of one stored index row (whole lane tiles of 128, zeros
        behind the ``indexWidth`` that mean something); 0 for a model
        with no selector, whose pool then has no third array."""
        return -(-self.indexWidth // 128) * 128

    @property
    def rowWidth(self) -> int:
        """Lanes of one stored row of a paged (or ring) layer: the one
        definition the pool, its sharding and its byte counts read."""
        if self.latentWidth:
            return -(-(self.latentWidth + self.ropeWidth) // 128) * 128
        return self.kvHeads * self.headSize

    @property
    def splitHeads(self) -> int:
        """Equal parts of a row's lanes that a tensor-parallel mesh may
        put on different devices: the heads; a latent row, which every
        head reads whole, is one."""
        return 1 if self.latentWidth else self.kvHeads

    @property
    def pagedPools(self) -> int:
        """Arrays a paged layer's rows live in: K and V, or the one
        latent pool."""
        return 1 if self.latentWidth else 2

    @property
    def arrayKinds(self) -> Tuple[str, ...]:
        """The kind of every array of this model's pool, in the order the
        step and the admission write take and return them: ``"paged"``
        (K and V, or the one latent pool), ``"index"``, ``"ring"`` (K
        then V), ``"slot"`` (one for each entry of ``slotState``).  The
        one definition of that order: ``KVCachePool`` allocates by it
        and :class:`~deeplearning4j_tpu.nlp.served.ServedLM` builds the
        step and the write by it."""
        return (("paged",) * self.pagedPools
                + ("index",) * bool(self.indexWidth)
                + ("ring",) * (2 * bool(self.ringLayers))
                + ("slot",) * len(self.slotState))


def paged_rows_write(pool, stack, pageIds):
    """Copy one sequence's stacked prefill rows ((L, h, Tp, d), ``Tp`` a
    page multiple) into the pages of a token-major pool ((L, numPages,
    pageSize, h*d)) named by ``pageIds`` ((Tp/pageSize,) int32)."""
    L, h, Tp, d = stack.shape
    ps = pool.shape[2]
    return pool.at[:, pageIds].set(stack.transpose(0, 2, 1, 3).reshape(
        L, Tp // ps, ps, h * d).astype(pool.dtype))


@dataclasses.dataclass
class SelfAttentionLayer(BaseLayer):
    """Per-timestep self-attention over the sequence.

    Reference: ``conf/layers/SelfAttentionLayer.java``.  Input (b, nIn, t) →
    output (b, nOut, t).  ``projectInput`` must be true when nHeads > 1
    (matching the reference's validation).

    ``causal=True`` masks attention to past-and-self (decoder style).
    """
    nIn: int = 0
    nOut: int = 0
    nHeads: int = 1
    headSize: int = 0
    projectInput: bool = True
    causal: bool = False

    def preferredFormat(self):
        return "RNN"

    def inferNIn(self, inputType):
        if not self.nIn:
            self.nIn = inputType.size
        if not self.headSize:
            self.headSize = (self.nOut or self.nIn) // self.nHeads
        if not self.nOut:
            self.nOut = self.nIn if not self.projectInput \
                else self.nHeads * self.headSize

    def getOutputType(self, inputType):
        return InputType.recurrent(self.nOut, inputType.timeSeriesLength)

    def weightParamKeys(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def initParams(self, key, inputType, dtype=jnp.float32):
        if not self.projectInput:
            if self.nHeads > 1:  # matches the reference's validation
                raise ValueError(
                    "projectInput=False requires nHeads == 1")
            return {}
        d = self.nHeads * self.headSize
        wi = self.weightInit or "XAVIER"
        ks = jax.random.split(key, 4)
        return {"Wq": init_weight(ks[0], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wk": init_weight(ks[1], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wv": init_weight(ks[2], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wo": init_weight(ks[3], (d, self.nOut), d, self.nOut, wi,
                                  dtype)}

    acceptsMask = True

    def forward(self, params, x, train, key, state, mask=None):
        x = self._dropin(x, train, key)
        xt = jnp.transpose(x, (0, 2, 1))             # (b, t, nIn)
        if self.projectInput:
            y = _mha(xt, params["Wq"], params["Wk"], params["Wv"],
                     params["Wo"], self.nHeads, mask, causal=self.causal)
        else:
            eye = jnp.eye(self.nIn, dtype=xt.dtype)
            y = _mha(xt, eye, eye, eye, eye, 1, mask, causal=self.causal)
        return jnp.transpose(y, (0, 2, 1)), state


@dataclasses.dataclass
class LearnedSelfAttentionLayer(BaseLayer):
    """Attention with nQueries LEARNED query vectors: pools a variable-length
    sequence to a fixed (b, nOut, nQueries) output.

    Reference: ``conf/layers/LearnedSelfAttentionLayer.java``.
    """
    nIn: int = 0
    nOut: int = 0
    nHeads: int = 1
    headSize: int = 0
    nQueries: int = 1
    projectInput: bool = True

    def preferredFormat(self):
        return "RNN"

    def inferNIn(self, inputType):
        if not self.nIn:
            self.nIn = inputType.size
        if not self.headSize:
            self.headSize = (self.nOut or self.nIn) // self.nHeads
        if not self.nOut:
            self.nOut = self.nIn if not self.projectInput \
                else self.nHeads * self.headSize

    def getOutputType(self, inputType):
        return InputType.recurrent(self.nOut, self.nQueries)

    def weightParamKeys(self):
        return ("Wq", "Wk", "Wv", "Wo", "Q")

    def initParams(self, key, inputType, dtype=jnp.float32):
        if not self.projectInput and self.nHeads > 1:
            raise ValueError("projectInput=False requires nHeads == 1")
        ks = jax.random.split(key, 5)
        wi = self.weightInit or "XAVIER"
        p = {"Q": init_weight(ks[4], (self.nIn, self.nQueries), self.nIn,
                              self.nQueries, wi, dtype)}
        if self.projectInput:
            d = self.nHeads * self.headSize
            p.update({
                "Wq": init_weight(ks[0], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wk": init_weight(ks[1], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wv": init_weight(ks[2], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wo": init_weight(ks[3], (d, self.nOut), d, self.nOut, wi,
                                  dtype)})
        return p

    acceptsMask = True

    def forward(self, params, x, train, key, state, mask=None):
        x = self._dropin(x, train, key)
        xt = jnp.transpose(x, (0, 2, 1))             # (b, t, nIn)
        b = xt.shape[0]
        q = jnp.broadcast_to(params["Q"].T[None], (b, self.nQueries, self.nIn))
        if self.projectInput:
            y = _mha(xt, params["Wq"], params["Wk"], params["Wv"],
                     params["Wo"], self.nHeads, mask, q_btn=q)
        else:
            eye = jnp.eye(self.nIn, dtype=xt.dtype)
            y = _mha(xt, eye, eye, eye, eye, 1, mask, q_btn=q)
        return jnp.transpose(y, (0, 2, 1)), state    # (b, nOut, nQueries)


@dataclasses.dataclass
class RecurrentAttentionLayer(BaseLayer):
    """Recurrent cell whose per-timestep input is augmented with an attention
    readout over the whole input sequence.

    Reference: ``conf/layers/RecurrentAttentionLayer.java`` (SimpleRnn-style
    recurrence + attention per step).  Output (b, nOut, t).  The recurrence
    runs as ``lax.scan`` (compiler-friendly control flow); the attention
    context for ALL timesteps is computed as one batched einsum BEFORE the
    scan — O(t²) matmul on the MXU instead of t sequential attention calls.
    """
    nIn: int = 0
    nOut: int = 0
    nHeads: int = 1
    headSize: int = 0
    projectInput: bool = True

    def preferredFormat(self):
        return "RNN"

    def inferNIn(self, inputType):
        if not self.nIn:
            self.nIn = inputType.size
        if not self.headSize:
            self.headSize = (self.nOut or self.nIn) // self.nHeads

    def getOutputType(self, inputType):
        return InputType.recurrent(self.nOut, inputType.timeSeriesLength)

    def weightParamKeys(self):
        return ("W", "RW", "Wq", "Wk", "Wv", "Wo")

    def initParams(self, key, inputType, dtype=jnp.float32):
        ks = jax.random.split(key, 7)
        wi = self.weightInit or "XAVIER"
        # context width: projected = nHeads*headSize, unprojected = nIn
        d = self.nHeads * self.headSize if self.projectInput else self.nIn
        if not self.projectInput and self.nHeads > 1:
            raise ValueError("projectInput=False requires nHeads == 1")
        p = {"W": init_weight(ks[0], (self.nIn + d, self.nOut),
                              self.nIn + d, self.nOut, wi, dtype),
             "RW": init_weight(ks[1], (self.nOut, self.nOut), self.nOut,
                               self.nOut, wi, dtype),
             "b": jnp.zeros((self.nOut,), dtype)}
        if self.projectInput:
            p.update({
                "Wq": init_weight(ks[2], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wk": init_weight(ks[3], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wv": init_weight(ks[4], (self.nIn, d), self.nIn, d, wi, dtype),
                "Wo": init_weight(ks[5], (d, d), d, d, wi, dtype)})
        return p

    acceptsMask = True

    def forward(self, params, x, train, key, state, mask=None):
        from deeplearning4j_tpu.nn.activations import get_activation
        x = self._dropin(x, train, key)
        xt = jnp.transpose(x, (0, 2, 1))             # (b, t, nIn)
        if self.projectInput:
            ctx = _mha(xt, params["Wq"], params["Wk"], params["Wv"],
                       params["Wo"], self.nHeads, mask)  # (b, t, d)
        else:
            eye = jnp.eye(self.nIn, dtype=xt.dtype)
            ctx = _mha(xt, eye, eye, eye, eye, 1, mask)
        inp = jnp.concatenate([xt, ctx], axis=-1)    # (b, t, nIn+d)
        act = get_activation(self.activation or "tanh")
        pre = jnp.einsum("btn,no->bto", inp, params["W"]) + params["b"]

        def cell(h, pre_t):
            h = act(pre_t + jnp.matmul(h, params["RW"]))
            return h, h

        h0 = jnp.zeros((xt.shape[0], self.nOut), xt.dtype)
        _, ys = jax.lax.scan(cell, h0, jnp.transpose(pre, (1, 0, 2)))
        y = jnp.transpose(ys, (1, 2, 0))             # (b, nOut, t)
        if mask is not None:
            y = y * mask[:, None, :].astype(y.dtype)
        return y, state


@dataclasses.dataclass
class KerasMultiHeadAttention(BaseLayer):
    """Keras-``MultiHeadAttention``-shaped self-attention: per-head q/k/v
    projections with biases and a combining output projection, parameters
    laid out exactly as keras stores them — query/key kernels
    ``(nIn, h, keyDim)``, value ``(nIn, h, valueDim)``, output
    ``(h, valueDim, nOut)`` — so imported weights copy in directly
    (``imports/keras_import.py``).  Input/output follow the DL4J RNN
    convention (b, n, t); the score chain dispatches through
    ``parallel.ring.dot_product_attention`` (flash on TPU for long T).
    """
    nIn: int = 0
    nHeads: int = 1
    keyDim: int = 0
    valueDim: int = 0          # 0 -> keyDim
    nOut: int = 0              # 0 -> nIn
    hasBias: bool = True

    acceptsMask = True

    def preferredFormat(self):
        return "RNN"

    def inferNIn(self, inputType):
        if not self.nIn:
            self.nIn = inputType.size
        if not self.valueDim:
            self.valueDim = self.keyDim
        if not self.nOut:
            self.nOut = self.nIn

    def getOutputType(self, inputType):
        return InputType.recurrent(self.nOut or self.nIn,
                                   inputType.timeSeriesLength)

    def weightParamKeys(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def initParams(self, key, inputType, dtype=jnp.float32):
        h, dk, dv = self.nHeads, self.keyDim, self.valueDim or self.keyDim
        wi = self.weightInit or "XAVIER"
        ks = jax.random.split(key, 4)
        p = {"Wq": init_weight(ks[0], (self.nIn, h, dk), self.nIn, h * dk,
                               wi, dtype),
             "Wk": init_weight(ks[1], (self.nIn, h, dk), self.nIn, h * dk,
                               wi, dtype),
             "Wv": init_weight(ks[2], (self.nIn, h, dv), self.nIn, h * dv,
                               wi, dtype),
             "Wo": init_weight(ks[3], (h, dv, self.nOut), h * dv, self.nOut,
                               wi, dtype)}
        if self.hasBias:
            p["bq"] = jnp.zeros((h, dk), dtype)
            p["bk"] = jnp.zeros((h, dk), dtype)
            p["bv"] = jnp.zeros((h, dv), dtype)
            p["bo"] = jnp.zeros((self.nOut,), dtype)
        return p

    def forward(self, params, x, train, key, state, mask=None):
        from deeplearning4j_tpu.parallel.ring import dot_product_attention
        x = self._dropin(x, train, key)
        xt = jnp.transpose(x, (0, 2, 1))                   # (b, t, nIn)
        q = jnp.einsum("btf,fhk->bthk", xt, params["Wq"])
        k = jnp.einsum("btf,fhk->bthk", xt, params["Wk"])
        v = jnp.einsum("btf,fhv->bthv", xt, params["Wv"])
        if self.hasBias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        # (b, t, h, d) -> (b, h, t, d) for the shared dispatch point
        ctx = dot_product_attention(q.transpose(0, 2, 1, 3),
                                    k.transpose(0, 2, 1, 3),
                                    v.transpose(0, 2, 1, 3), mask=mask)
        y = jnp.einsum("bhtv,hvo->bto", ctx, params["Wo"])
        if self.hasBias:
            y = y + params["bo"]
        return jnp.transpose(y, (0, 2, 1)), state


for _c in [SelfAttentionLayer, LearnedSelfAttentionLayer,
           RecurrentAttentionLayer, KerasMultiHeadAttention]:
    register_layer(_c)
