"""Multi-head latent attention (arXiv:2405.04434) as the served models
that have it run it: what ``PanguMoELM`` and ``LingLM`` share between a
layer's queries, which are each model's own, and its output projection.

``[c_kv | k_r] = h W_dkv`` with ``c_kv = RMSNorm(c_kv)`` and ONE rotated
``k_r`` for all heads; ``k_nope = c_kv W_uk``, ``v = c_kv W_uv`` a head;
``s = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``.  Two
arithmetic forms.  Forward and prefill run it as written (UNABSORBED:
keys and values of every position are formed; :meth:`_latent_full`).  The
decode step runs it ABSORBED (:meth:`_latent_step`): ``q~ = q_nope
W_uk^T`` a head, scores ``q~ . c_kv + q_rope . k_r`` against the cached
rows themselves, ``o = (softmax(s) c_kv) W_uv``; so what a position keeps
is one LATENT ROW ``[c_kv | k_r]`` (after norm and rotation), from which
keys and values both come: the model's ``cacheSpec()`` names it, the
scheduler's pool holds one array of them, and
:func:`~deeplearning4j_tpu.nn.conf.attention.paged_latent_attention`
reads it (on one TPU the kernel over the live pages).

:class:`LatentAttention` is a mixin over a model's ``config`` (``nHeads``,
``kvRank``, ``nopeDim``, ``ropeDim``, ``vDim``, ``ropeTheta``, ``eps``)
and ``cacheSpec()``; a layer's parameters are ``Wdkv (d, kvRank +
ropeDim)``, ``kvnorm (kvRank,)`` and, head-major as the step's matmuls a
head read them, ``Wuk (H, kvRank, nopeDim)`` and ``Wuv (H, vDim,
kvRank)``, each contracted over its minor dimension.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.attention import paged_latent_attention
from deeplearning4j_tpu.nlp.mamba import _mm, _rms
from deeplearning4j_tpu.nlp.served import _rope
from deeplearning4j_tpu.parallel.ring import (_FLASH_MIN_T, _flash_refusal,
                                              flash_attention)

__all__ = ["LatentAttention"]

_F32 = jnp.float32
_I32 = jnp.int32
_NEG = -1e30
#: queries a block of the full-sequence attention holds against every
#: key: 128 heads of float32 scores over 4,096 keys are 0.54 GB a block
_QUERY_BLOCK = 256


class LatentAttention:
    """The latent row, the unabsorbed attention over whole sequences and
    the absorbed step, for a served model to inherit."""

    def _rotate(self, x, p):
        """Rotary positions ``p (...)`` on ``x (..., rope)`` float32:
        lane ``i`` pairs with lane ``i + rope / 2``
        (:func:`~deeplearning4j_tpu.nlp.served._rope`).  A model whose
        checkpoint pairs its lanes otherwise overrides this."""
        return _rope(x, p, self.config.ropeTheta)

    def _row_wide(self, a):
        """``a (..., latent + rope)`` with zeros behind, to the width of a
        stored row (whole lane tiles)."""
        pad = self.cacheSpec().rowWidth - a.shape[-1]
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))

    def _latent_row(self, lp, h, p, dtype):
        """The row a position keeps, as it is stored: ``[RMSNorm(c_kv) |
        RoPE(k_r) | zeros to whole lane tiles]``."""
        c = self.config
        ckr = _mm(h, lp["Wdkv"])
        return self._row_wide(jnp.concatenate(
            [_rms(ckr[..., :c.kvRank], lp["kvnorm"], c.eps),
             self._rotate(ckr[..., c.kvRank:], p)], axis=-1)
        ).astype(dtype)

    def _latent_full(self, lp, qn, qr, row, start):
        """The layer's attention over whole LEFT-padded sequences,
        unabsorbed: ``qn (b, T, H, nope)``, ``qr (b, T, H, rope)`` float32
        (rotated), ``row (b, T, W)`` the positions' stored rows.  Returns
        ``(b, T, H vDim)`` float32, before ``W_o``."""
        c = self.config
        cd = row.dtype
        ckv, kr = row[..., :c.kvRank], row[..., c.kvRank:c.kvRank
                                           + c.ropeDim]
        heads = lambda eq, W: jnp.einsum(
            eq, ckv, W, preferred_element_type=_F32).astype(cd)
        return self._attend_full(
            qn, qr, heads("btr,hrd->bthd", lp["Wuk"]), kr,
            heads("btr,hdr->bthd", lp["Wuv"]), start)

    def _attend_full(self, qn, qr, kn, kr, v, start):
        """Causal softmax attention over whole sequences with every key
        and value formed: ``qn (b, T, H, nope)``, ``qr (b, T, H, rope)``
        float32; ``kn (b, T, H, nope)``, ``kr (b, T, rope)`` (one for all
        heads), ``v (b, T, H, vDim)`` in the stream's dtype.  A block of
        queries at a time against every key; no key before ``start`` is
        valid."""
        c = self.config
        b, T = qn.shape[:2]
        cd = v.dtype
        if T >= _FLASH_MIN_T and _flash_refusal(T, T) is None:
            return self._attend_flash(qn, qr, kn, kr, v, start)
        B = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T
        qn, qr = qn.astype(cd), qr.astype(cd)
        kpos = jnp.arange(T, dtype=_I32)[None, None, :]
        real = kpos >= start[:, None, None]                  # (b, 1, T)
        scale = (c.nopeDim + c.ropeDim) ** -0.5

        def block(i):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * B, B, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", cut(qn), kn,
                           preferred_element_type=_F32) \
                + jnp.einsum("bqhd,bkd->bhqk", cut(qr), kr,
                             preferred_element_type=_F32)
            rows = i * B + jnp.arange(B, dtype=_I32)
            valid = (kpos <= rows[None, :, None]) & real     # (b, B, T)
            a = jax.nn.softmax(jnp.where(valid[:, None], s * scale, _NEG),
                               axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", a.astype(cd), v,
                              preferred_element_type=_F32)
        o = jax.lax.map(block, jnp.arange(T // B, dtype=_I32))
        return jnp.moveaxis(o, 0, 1).reshape(b, T, c.nHeads * c.vDim)

    def _attend_flash(self, qn, qr, kn, kr, v, start, interpret=False):
        """:meth:`_attend_full` through the flash kernel
        (``parallel/ring.py``), which holds no score outside VMEM and
        skips the blocks above the diagonal; chosen as the attention
        layers choose it: on a TPU, from 1,024 positions, at lengths its
        blocks divide.  The kernel is causal and takes no key mask, so
        every sequence is turned until its real tokens come FIRST and its
        pads lie behind them, where no real query looks; the output is
        turned back.  Heads lead, the lanes are padded to whole tiles
        (192 -> 256 for queries and keys, 128 -> 256 for values) and the
        queries carry the difference between the kernel's scale
        (lanes^-1/2) and the model's.  ``interpret`` is for tests."""
        c = self.config
        b, T, H, _ = qn.shape
        cd = v.dtype
        d = c.nopeDim + c.ropeDim
        D = -(-d // 128) * 128
        turn = jax.vmap(lambda a, by: jnp.roll(a, by, axis=0))

        def laid(a):
            a = jnp.pad(a.astype(cd), ((0, 0),) * 3 + ((0, D - a.shape[-1]),))
            return turn(a, -start).transpose(0, 2, 1, 3)     # (b, H, T, D)
        q = jnp.concatenate([qn, qr], axis=-1) * (D / d) ** 0.5
        k = jnp.concatenate([kn, jnp.broadcast_to(
            kr[:, :, None], (b, T, H, c.ropeDim))], axis=-1)
        o = flash_attention(laid(q), laid(k), laid(v), causal=True,
                            interpret=interpret)[..., :c.vDim]
        return turn(o.transpose(0, 2, 1, 3), start).reshape(
            b, T, H * c.vDim).astype(_F32)

    def _latent_step(self, lp, qn, qr, rowNew, rows, li, pageTable, pos,
                     start):
        """The layer's attention for one token a slot, ABSORBED, against
        the pool's latent rows: ``qn (S, H, nope)``, ``qr (S, H, rope)``
        float32 (rotated), ``rowNew (S, W)`` the new position's row,
        which is written to layer ``li`` of ``rows`` first.  Returns ``(o
        (S, H, vDim) float32 before W_o, rows)``."""
        c = self.config
        cd = rows.dtype
        # q~ = q_nope W_uk^T a head: the query in the latent's lanes
        qa = jnp.einsum("shd,hrd->shr", qn.astype(cd), lp["Wuk"],
                        preferred_element_type=_F32)
        qh = self._row_wide(jnp.concatenate([qa, qr], axis=-1))
        ctx, rows = paged_latent_attention(
            qh[:, :, None], rowNew[:, None], rows, li, pageTable, pos, start,
            valueWidth=c.kvRank, scale=(c.nopeDim + c.ropeDim) ** -0.5)
        return jnp.einsum("shr,hdr->shd", ctx[:, :, 0].astype(cd),
                          lp["Wuv"], preferred_element_type=_F32), rows
