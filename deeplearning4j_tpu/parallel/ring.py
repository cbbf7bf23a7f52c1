"""Ring attention + memory-efficient attention — sequence/context parallelism.

Reference: the reference has NO sequence parallelism (SURVEY.md §5.7 — long
sequences are handled only by TBPTT + masking; its attention ops —
``libnd4j ops/declarable/generic/nn/multi_head_dot_product_attention.cpp``,
wrapped by ``SelfAttentionLayer`` et al. — materialise O(T²) scores on one
device).  This module is the NEW capability the TPU build adds on top of
parity: sequences scale across chips over the ``seq`` mesh axis.

Three implementations of softmax(QKᵀ/√d)·V, one semantics:

- :func:`blockwise_attention` — pure-XLA online-softmax over K/V blocks via
  ``lax.scan``: O(T) memory, runs anywhere, and is the building block of the
  ring.
- :func:`flash_attention` — Pallas TPU kernel (grid over (batch·heads,
  q-blocks, k-blocks), f32 accumulators in VMEM scratch); the single-chip hot
  path.  TPU only: it raises where it cannot run, and
  :func:`dot_product_attention` is where another implementation is chosen.
- :func:`ring_attention` — called under ``shard_map`` with Q/K/V sharded on
  the time dimension over a mesh axis: each step computes one local block
  update, then rotates K/V one hop around the ring with ``lax.ppermute``
  (ICI neighbour exchange), overlapping compute with the collective.

Layout is (batch, heads, time, head_dim) throughout.  Masks are (batch, t_k)
with 1 = valid key, matching the DL4J mask convention.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

__all__ = ["blockwise_attention", "flash_attention", "ring_attention",
           "context_parallel_attention", "dot_product_attention"]

_NEG = -1e30  # additive-mask floor; avoids -inf NaN paths in exp/grad


def _scale(q):
    return 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))


def _block_update(q, k, v, o, l, m, bias):
    """One online-softmax accumulation step over a K/V block.

    q: (..., tq, d); k/v: (..., tk, d); o: (..., tq, d) f32;
    l/m: (..., tq, 1) f32; bias: broadcastable to (..., tq, tk) additive.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k,
                   preferred_element_type=jnp.float32) * _scale(q)
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1, keepdims=True)
    # p in the storage dtype keeps the second matmul on the full-rate MXU
    # path (f32 operands quarter the systolic-array throughput)
    o = o * corr + jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)
    return o, l, m_new


def _finish(o, l):
    return o / jnp.maximum(l, 1e-30)


def _mask_bias(mask, dtype=jnp.float32):
    """(b, tk) 1=valid → additive (b, 1, 1, tk)."""
    if mask is None:
        return None
    m = mask.astype(bool)[:, None, None, :]
    return jnp.where(m, 0.0, _NEG).astype(dtype)


def blockwise_attention(q, k, v, mask=None, causal: bool = False,
                        block_k: int = 512):
    """Memory-efficient attention: ``lax.scan`` over K/V blocks with an
    online softmax — never materialises the (tq, tk) score matrix beyond one
    block.  Exact (not approximate) w.r.t. dense softmax attention.

    q/k/v: (b, h, t, d); mask: (b, tk) 1=valid; returns (b, h, tq, d) in
    q.dtype.
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_k = min(block_k, tk)
    nblocks = -(-tk // block_k)
    pad = nblocks * block_k - tk
    kmask = jnp.ones((b, tk), dtype=bool) if mask is None \
        else mask.astype(bool)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kmask = jnp.pad(kmask, ((0, 0), (0, pad)))
    ks = k.reshape(b, h, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, h, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    ms = kmask.reshape(b, nblocks, block_k).transpose(1, 0, 2)

    q_pos = jnp.arange(tq)[:, None]

    def step(carry, xs):
        o, l, m = carry
        kb, vb, mb, ki = xs
        bias = jnp.where(mb[:, None, None, :], 0.0, _NEG)
        if causal:
            k_pos = ki * block_k + jnp.arange(block_k)[None, :]
            bias = bias + jnp.where(k_pos <= q_pos, 0.0, _NEG)
        o, l, m = _block_update(q, kb, vb, o, l, m, bias)
        return (o, l, m), None

    o0 = jnp.zeros((b, h, tq, d), jnp.float32)
    l0 = jnp.zeros((b, h, tq, 1), jnp.float32)
    m0 = jnp.full((b, h, tq, 1), _NEG, jnp.float32)
    (o, l, _), _ = lax.scan(step, (o0, l0, m0),
                            (ks, vs, ms, jnp.arange(nblocks)))
    return _finish(o, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention kernel (TPU)
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, causal: bool, block_q: int, block_k: int,
                  nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, jnp.float32(_NEG))
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qi = pl.program_id(1)

    def _compute():
        # keep q/k/v in their storage dtype (bf16) for the MXU dots —
        # f32 operands would run the systolic array at quarter rate; the
        # products still accumulate in f32 via preferred_element_type
        q = q_ref[0]                               # (block_q, d)
        k = k_ref[0]                               # (block_k, d)
        v = v_ref[0]
        # f32 literals throughout — the package enables x64, so a bare python
        # float would be f64 in-kernel, which Mosaic cannot legalize
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(1.0 / (q.shape[-1] ** 0.5))
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, jnp.float32(_NEG))

        m_prev = m_ref[:, :1]                      # (block_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True), l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        # p cast to the storage dtype for the second MXU dot (standard
        # flash practice; the f32 accumulator keeps the precision)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Skip fully-future k blocks: no query row in this q block can see
        # any key in them, so the whole (QKᵀ, exp, PV) is wasted MXU work.
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _fin():
        l_fin = jnp.maximum(l_ref[:, :1], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l_fin).astype(o_ref.dtype)
        # per-row logsumexp banked for the flash backward's p recompute
        # (lane-replicated to 128 — Mosaic block shapes need the trailing
        # dim divisible by 128, same layout as jax's shipped TPU kernel)
        lse_ref[0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l_fin),
                                      lse_ref[0].shape)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, causal: bool, block_q: int,
                         block_k: int, nk: int, scale: float):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(scale)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, jnp.float32(_NEG))
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * jnp.float32(scale)
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                          block_q: int, block_k: int, nq: int, scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(scale)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos <= q_pos, s, jnp.float32(_NEG))
        p = jnp.exp(s - lse_ref[0][:, :1])            # (bq, bk)
        pt = p.astype(do.dtype)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * jnp.float32(scale)
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


_FLASH_BLOCK = 1024     # default (block_q, block_k) edge
_FLASH_MIN_T = 1024     # "auto" prefers the XLA-fused dense chain below this


def _flash_refusal(tq: int, tk: int, block_q: int = _FLASH_BLOCK,
                   block_k: int = _FLASH_BLOCK,
                   interpret: bool = False) -> Optional[str]:
    """Why the Pallas kernel cannot serve these lengths here, or None when
    it can.  ``impl="auto"`` chooses with it; :func:`flash_attention`
    raises with it."""
    if tq % min(block_q, tq) or tk % min(block_k, tk):
        return (f"sequence lengths ({tq}, {tk}) are not multiples of the "
                f"({min(block_q, tq)}, {min(block_k, tk)}) blocks")
    if not interpret and jax.default_backend() != "tpu":
        return f"the platform is {jax.default_backend()!r}, not 'tpu'"
    return None


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = _FLASH_BLOCK, block_k: int = _FLASH_BLOCK,
                    interpret: bool = False):
    """Pallas TPU flash attention.  q/k/v: (b, h, t, d).

    Grid (b·h, q-blocks, k-blocks); the k dimension is sequential so the
    online-softmax accumulators live in VMEM scratch across k steps.
    Raises ``ValueError`` off TPU or when a length is not a multiple of
    its block — it never computes with another implementation
    (``interpret`` runs the same kernels in the Pallas interpreter and is
    for tests only).

    Differentiable with FLASH backward kernels: the forward also banks the
    per-row logsumexp; the backward recomputes p block-by-block in two
    Pallas passes (dk/dv with the q-axis sequential, dq with the k-axis
    sequential) — no O(T²) residuals are ever stored.
    """
    tq, tk = q.shape[2], k.shape[2]
    why = _flash_refusal(tq, tk, block_q, block_k, interpret)
    if why is not None:
        raise ValueError(f"flash_attention cannot run: {why}")
    return _flash(q, k, v, causal, min(block_q, tq), min(block_k, tk),
                  interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    b, h, tq, d = q.shape
    # delta_i = rowsum(dO ⊙ O): one fused elementwise+reduce in XLA,
    # lane-replicated to the same (b·h, tq, 128) layout as lse
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1).reshape(b * h, tq)[:, :, None], (b * h, tq, 128))
    dq, dk, dv = _flash_backward(q, k, v, g, lse, delta, causal,
                                 block_q, block_k, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k

    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)

    kern = functools.partial(_flash_kernel, causal=causal, block_q=block_q,
                             block_k=block_k, nk=nk)
    # The package enables jax_enable_x64 (DL4J double-precision semantics);
    # a bare literal 0 in an index map would then trace as i64, which Mosaic
    # cannot legalize (and index maps may not capture array constants) —
    # ``ki * 0`` stays i32 because program ids are i32 and the weak python
    # int does not promote.
    out, lse = pl.pallas_call(
        kern,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, ki * 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, qi * 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, qi * 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, ki * 0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda bh, qi, ki: (bh, qi, ki * 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, tq, d), lse


def _flash_backward(q, k, v, g, lse, delta, causal, block_q, block_k,
                    interpret):
    """Two-pass Pallas flash backward: dq with the k axis sequential;
    dk/dv with the q axis sequential.  p is recomputed per block from the
    banked logsumexp — no O(T²) residuals."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    scale = 1.0 / (d ** 0.5)

    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    gf = g.astype(q.dtype).reshape(b * h, tq, d)

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, ki * 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, qi * 0))
    r_spec = pl.BlockSpec((1, block_q, 128),
                          lambda bh, qi, ki: (bh, qi, ki * 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          scale=scale),
        grid=(b * h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)

    # dk/dv: k blocks parallel, q axis sequential (grid order bh, ki, qi)
    q_spec2 = pl.BlockSpec((1, block_q, d),
                           lambda bh, ki, qi: (bh, qi, ki * 0))
    k_spec2 = pl.BlockSpec((1, block_k, d),
                           lambda bh, ki, qi: (bh, ki, qi * 0))
    r_spec2 = pl.BlockSpec((1, block_q, 128),
                           lambda bh, ki, qi: (bh, qi, ki * 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          scale=scale),
        grid=(b * h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, gf, lse, delta)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


# ---------------------------------------------------------------------------
# Ring attention (sequence/context parallel)
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, axis_name: str = "seq", axis_size: int = None,
                   mask=None, causal: bool = False):
    """Exact attention with Q/K/V sharded on time over ``axis_name``.

    Must be called inside ``shard_map`` (see
    :func:`context_parallel_attention` for the wrapper).  Each of the
    ``axis_size`` steps computes the online-softmax update of the local Q
    block against the currently-held K/V block, then rotates K/V one hop
    around the ring with ``lax.ppermute`` — the XLA collective rides ICI
    neighbour links and overlaps with the next block's compute.

    q/k/v: (b, h, t_local, d); mask: (b, t_local) for the LOCAL key block.
    """
    if axis_size is None:
        axis_size = int(lax.psum(1, axis_name))
    my = lax.axis_index(axis_name)
    b, h, t_loc, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o = jnp.zeros((b, h, t_loc, d), jnp.float32)
    l = jnp.zeros((b, h, t_loc, 1), jnp.float32)
    m = jnp.full((b, h, t_loc, 1), _NEG, jnp.float32)
    q_pos = (my * t_loc + jnp.arange(t_loc))[:, None]

    kk, vv, mm = k, v, mask
    for i in range(axis_size):
        src = (my - i) % axis_size          # which shard's K/V we now hold
        bias = None
        if mm is not None:
            bias = jnp.where(mm.astype(bool)[:, None, None, :], 0.0, _NEG)
        if causal:
            k_pos = src * t_loc + jnp.arange(t_loc)[None, :]
            cb = jnp.where(k_pos <= q_pos, 0.0, _NEG)
            bias = cb if bias is None else bias + cb
        o, l, m = _block_update(q, kk, vv, o, l, m, bias)
        if i != axis_size - 1:
            kk = lax.ppermute(kk, axis_name, perm)
            vv = lax.ppermute(vv, axis_name, perm)
            if mm is not None:
                mm = lax.ppermute(mm, axis_name, perm)
    return _finish(o, l).astype(q.dtype)


def context_parallel_attention(mesh, q, k, v, mask=None, causal: bool = False,
                               axis_name: str = "seq"):
    """Run :func:`ring_attention` over the ``seq`` axis of a mesh.

    ``mesh`` is a ``jax.sharding.Mesh`` or ``parallel.DeviceMesh``; q/k/v are
    GLOBAL (b, h, t, d) arrays (t divisible by the seq-axis size); batch is
    sharded over ``data`` if that axis exists.
    """
    jmesh = getattr(mesh, "mesh", mesh)
    axis_size = jmesh.shape[axis_name]
    batch_axis = "data" if "data" in jmesh.shape else None
    spec = P(batch_axis, None, axis_name, None)
    mspec = P(batch_axis, axis_name)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           axis_size=axis_size, causal=causal)

    if mask is None:
        # No mask operand at all: ring_attention's mm-is-None fast path skips
        # the per-hop mask ppermute and bias construction entirely.
        sharded = jax.shard_map(lambda a, b_, c: fn(a, b_, c, mask=None),
                                mesh=jmesh, in_specs=(spec, spec, spec),
                                out_specs=spec)
        return sharded(q, k, v)
    sharded = jax.shard_map(lambda a, b_, c, m_: fn(a, b_, c, mask=m_),
                            mesh=jmesh, in_specs=(spec, spec, spec, mspec),
                            out_specs=spec)
    return sharded(q, k, v, mask)


def dot_product_attention(qh, kh, vh, mask=None, causal: bool = False,
                          impl: str = "auto"):
    """Dispatch point used by the attention layers (``nn/conf/attention.py``).

    impl: "dense" (materialised softmax — reference semantics,
    ``multi_head_dot_product_attention``), "blockwise", "flash", "ring"
    (sequence-parallel over the active mesh's seq axis), or "auto".
    A named impl runs or raises; only "auto" chooses, from what it can
    observe: ring when a ParallelWrapper fit is compiling against a mesh
    with a seq axis; on TPU from ``_FLASH_MIN_T`` up, the flash kernel
    for unmasked block-multiple lengths and blockwise for unmasked
    others; dense otherwise (XLA fuses the small case fine, and dense
    honors a key mask exactly).
    """
    if impl == "auto":
        from deeplearning4j_tpu.parallel.mesh import active_mesh
        am = active_mesh()
        tq, tk = qh.shape[2], kh.shape[2]
        if am is not None and getattr(am, "seqSize", 1) > 1 \
                and tq % am.seqSize == 0 and tk % am.seqSize == 0:
            impl = "ring"
        elif tq < _FLASH_MIN_T or mask is not None \
                or jax.default_backend() != "tpu":
            impl = "dense"
        else:
            impl = "flash" if _flash_refusal(tq, tk) is None else "blockwise"
    if impl == "ring":
        from deeplearning4j_tpu.parallel.mesh import active_mesh
        am = active_mesh()
        if am is None:
            raise ValueError("impl='ring' needs an active mesh "
                             "(ParallelWrapper.fit with a seq axis)")
        return context_parallel_attention(am, qh, kh, vh, mask=mask,
                                          causal=causal)
    if impl == "flash":
        if mask is not None:
            raise ValueError("impl='flash' cannot run: the kernel takes no "
                             "key mask")
        return flash_attention(qh, kh, vh, causal=causal)
    if impl == "blockwise":
        return blockwise_attention(qh, kh, vh, mask=mask, causal=causal)
    if impl != "dense":
        raise ValueError(f"unknown attention impl {impl!r}")
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * _scale(qh)
    if mask is not None:
        s = jnp.where(mask.astype(bool)[:, None, None, :], s,
                      jnp.asarray(_NEG, s.dtype))
    if causal:
        tq, tk = s.shape[-2:]
        cm = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        s = jnp.where(cm, s, jnp.asarray(_NEG, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vh)
