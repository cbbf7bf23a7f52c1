"""The gated delta rule of the serving tier's linear-attention layers, in
the two forms a served model needs and for the two decays its callers
have: ONE decay a head (Gated DeltaNet, arXiv:2412.06464: ``OlmoHybridLM``)
and one a CHANNEL (Kimi Delta Attention, arXiv:2510.26692: ``LingLM``).

A head keeps a float32 matrix ``S (dk, dv)``.  A position decays it,
corrects it by a rank-one delta and reads it::

    S' = Diag(a_t) S_{t-1}          a_t = exp(g_t): a scalar, or (dk,)
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

*The step* (:func:`delta_rule_step`) is that recurrence, one token a
slot.  *Forward and prefill* run its CHUNKED form
(:func:`delta_rule_chunked`): within a chunk of ``C`` positions the
rank-one updates are folded into matmuls by the UT transform, and only the
``(H, dk, dv)`` state is carried from chunk to chunk, so a prompt of
4,096 positions is 64 steps of a scan and not 4,096.

With ``G_i`` the running sum of ``g`` from the chunk's start, the chunk's
``u`` solve ``(I + A) u = diag(beta) (V - (exp(G) * K) S)`` with ``A_ij =
beta_i P_ij(K)`` below the diagonal, ``P_ij(X) = sum_c x_ic k_jc exp(G_ic -
G_jc)`` and ``S`` the state the chunk starts from.  So with ``T = (I +
A)^-1 diag(beta)``, ``W = T (exp(G) * K)`` and ``U = T V`` (no ``S`` in
them: computed for every chunk at once), the scan over chunks is ``u = U -
W S; o = (exp(G) * Q) S + (P(Q), lower) u; S <- exp(G_C) * S + (exp(G_C -
G) * K)^T u``.

The two decays differ in ``P`` alone.  With one decay a head ``exp(G_i -
G_j)`` is a ``(C, C)`` mask under ONE matmul ``X K^T``.  With one a
channel it is no mask: the decay has to ride on the operands, ``(x_i *
exp(G_i - R)) . (k_j * exp(R - G_j))`` against a reference row ``R``, and
one of the two factors grows as fast as the other shrinks.  The chunk is
therefore taken in SUB-BLOCKS of rows, each against the reference ``R_a``
= ``G`` of the row before it: its own rows' ``exp(G_i - R_a)`` and every
earlier column's ``exp(R_a - G_j)`` are at most 1, and only the columns
INSIDE the sub-block carry a factor above 1, at most ``exp(rows x
|lowerBound|)``.  That is what a decay with a lower bound (``g >=
lowerBound``, KDA's safe gate) buys: 16 rows at -5 a step are ``e^80``,
which float32 holds, where 64 rows would not be a number.

Everything here is float32 at ``HIGHEST`` matmul precision; a length that
is no multiple of the chunk is padded on the right with positions that
change nothing (``beta`` 0, ``g`` 0, ``k`` 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir

from deeplearning4j_tpu.nn.conf.attention import lowered_for_one_tpu

__all__ = ["delta_rule_chunked", "delta_rule_step", "delta_state_step",
           "l2_normalise", "short_conv_full", "short_conv_step"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: the largest exponent a sub-block's columns may carry: ``e^80`` times a
#: sum over the channels of normalised keys stays well inside float32
_EXP_ROOM = 80.0


def l2_normalise(x):
    """``x / sqrt(sum(x^2) + 1e-6)`` over the last axis (the
    flash-linear-attention layers' q/k norm; a zeroed row stays zero)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv_full(u, taps):
    """The depthwise causal convolution in front of q, k and v over whole
    sequences: ``u (b, T, c)`` float32, ``taps (K, c)``; tap ``K - 1``
    meets the position itself, zeros before the sequence.  Returns ``(the
    convolution (b, T, c), the last K - 1 inputs (b, K - 1, c))``: what a
    decode step's window continues from."""
    b, T, c = u.shape
    K = taps.shape[0]
    up = jnp.concatenate([jnp.zeros((b, K - 1, c), _F32), u], axis=1)
    t = taps.astype(_F32)
    return sum(t[j] * up[:, j:j + T] for j in range(K)), u[:, T - (K - 1):]


def short_conv_step(window, new, taps):
    """One position of :func:`short_conv_full` a slot: ``window (S, K - 1,
    c)`` the inputs before it, ``new (S, c)`` its own, ``taps (K, c)``.
    Returns ``(the convolution (S, c) float32, the window moved on by one
    (S, K - 1, c) float32)``."""
    full = jnp.concatenate([window.astype(_F32), new[:, None]], axis=1)
    return jnp.sum(full * taps.astype(_F32)[None], axis=1), full[:, 1:]


def _inv_unit_lower(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A (..., C, C)``,
    ``C`` a power of two, by doubling: with ``X`` the inverse of the
    diagonal blocks of size ``s``, the blocks of size ``2 s`` have the
    inverse ``[[X1, 0], [-X2 A21 X1, X2]]``, which is ``X - X A_off X``
    for ``A_off`` the ``A21`` corners alone.  ``log2 C`` rounds of two
    matmuls, no substitution row by row, and exact up to rounding (no
    power of ``A`` is ever formed)."""
    C = A.shape[-1]
    if C & (C - 1):
        raise ValueError(f"the chunk {C} is no power of two")
    i = np.arange(C)
    X = jnp.broadcast_to(jnp.eye(C, dtype=A.dtype), A.shape)
    s = 1
    while s < C:
        corner = (i[:, None] // (2 * s) == i[None, :] // (2 * s)) \
            & (i[:, None] % (2 * s) >= s) & (i[None, :] % (2 * s) < s)
        off = jnp.where(corner, A, 0)
        X = X - jnp.matmul(jnp.matmul(X, off, precision=_HI), X,
                           precision=_HI)
        s *= 2
    return X


def _sub_block_rows(chunk: int, lowerBound: float) -> int:
    """Rows of a sub-block of the per-channel chunk: the largest power of
    two whose rows, each decaying by ``lowerBound`` at most, stay within
    :data:`_EXP_ROOM`."""
    if lowerBound is None or not lowerBound < 0:
        raise ValueError(f"a decay a channel needs the lower bound of its "
                         f"g (a negative number), not {lowerBound!r}")
    rows = 1
    while 2 * rows <= chunk and 2 * rows * -lowerBound <= _EXP_ROOM:
        rows *= 2
    return rows


def _decayed_products(xs, k, G, rows: int):
    """``P(x) (..., C, C)`` for each ``x`` of ``xs``, ``x`` and ``k (...,
    C, dk)``, ``G (..., C, dk)`` the running sum of a decay a channel:
    ``P_ij(x) = sum_c x_ic k_jc exp(G_ic - G_jc)`` for ``j`` up to the end
    of ``i``'s sub-block of ``rows`` rows (what lies above the diagonal
    inside it is finite and the caller's mask drops it), 0 behind it."""
    C, dk = G.shape[-2:]
    A = C // rows
    lead = G.shape[:-2]
    # R_a: G of the row before sub-block a (the chunk's start for a = 0)
    R = jnp.concatenate([jnp.zeros(lead + (1, dk), _F32),
                         G[..., rows - 1:C - 1:rows, :]], axis=-2)
    mine = jnp.exp(G.reshape(lead + (A, rows, dk)) - R[..., :, None, :])
    col = np.arange(C)[None, :, None]
    upto = (np.arange(A)[:, None, None] + 1) * rows           # (A, 1, 1)
    theirs = jnp.exp(jnp.where(
        col < upto, R[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    khat = k[..., None, :, :] * theirs                        # (.., A, C, dk)
    return [jnp.einsum("...asc,...ajc->...asj",
                       x.reshape(lead + (A, rows, dk)) * mine, khat,
                       precision=_HI).reshape(lead + (C, C)) for x in xs]


def _head_decay(q, k, beta, g, mm):
    """``(A, M, gamma, Kh, gC)`` of every chunk for ONE decay a head,
    ``g (n, b, H, C)``: the decay between two rows is a ``(C, C)`` mask
    (taken in log space: no division by a ``gamma`` that has underflowed)
    under one matmul."""
    C = g.shape[-1]
    i = np.arange(C)
    G = jnp.cumsum(g, axis=-1)
    Gam = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                            G[..., :, None] - G[..., None, :],
                            -jnp.inf))                       # 0 above
    kT = jnp.swapaxes(k, -1, -2)
    A = jnp.where(i[:, None] > i[None, :],
                  beta[..., None] * mm(k, kT) * Gam, 0)
    return (A, mm(q, kT) * Gam, jnp.exp(G)[..., None],
            jnp.exp(G[..., -1:] - G)[..., None] * k,
            jnp.exp(G[..., -1])[..., None, None])            # (n, b, H, 1, 1)


def _channel_decay(q, k, beta, g, rows: int):
    """:func:`_head_decay` for one decay a CHANNEL, ``g (n, b, H, C,
    dk)``, the chunk in sub-blocks of ``rows`` rows."""
    C = g.shape[-2]
    i = np.arange(C)
    G = jnp.cumsum(g, axis=-2)
    Pk, Pq = _decayed_products((k, q), k, G, rows)
    A = jnp.where(i[:, None] > i[None, :], beta[..., None] * Pk, 0)
    return (A, jnp.where(i[:, None] >= i[None, :], Pq, 0), jnp.exp(G),
            jnp.exp(G[..., -1:, :] - G) * k,
            jnp.exp(G[..., -1, :])[..., None])               # (n, b, H, dk, 1)


def delta_rule_chunked(q, k, v, beta, g, chunk: int, lowerBound=None):
    """The gated delta rule over whole sequences, chunk by chunk.

    ``q, k (b, T, H, dk)``, ``v (b, T, H, dv)``, ``beta (b, T, H)`` and
    ``g = log a``, all float32; the state starts at zero.  ``g (b, T,
    H)`` is one decay a head; ``g (b, T, H, dk)`` one a channel, which
    needs ``lowerBound``, the bound its gate keeps ``g`` above (see the
    module's docstring): a ``g`` below it is refused, where its values
    can be seen (outside a trace), instead of coming back as infinities.
    Returns ``(o (b, T, H, dv), S_T (b, H, dk, dv))``."""
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk)
    channels = g.ndim == 4
    if channels:
        sub = _sub_block_rows(C, lowerBound)
        if not isinstance(g, jax.core.Tracer) and g.size \
                and float(jnp.min(g)) < lowerBound:
            raise ValueError(
                f"g reaches {float(jnp.min(g)):.4g}, below the bound "
                f"{lowerBound} that this chunk's sub-blocks of {sub} rows "
                "were sized for: its exponentials would be no float32")
    pad = -T % C
    if pad:
        z = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, beta, g = z(q), z(k), z(v), z(beta), z(g)
    n = (T + pad) // C
    # chunk-major, heads before rows: (n, b, H, C, ...)
    rows = lambda a: a.reshape(b, n, C, H, -1).transpose(1, 0, 3, 2, 4)
    q, k, v = rows(q), rows(k), rows(v)
    beta = rows(beta)[..., 0]                                # (n, b, H, C)
    mm = functools.partial(jnp.matmul, precision=_HI)
    A, M, gamma, Kh, gC = _channel_decay(q, k, beta, rows(g), sub) \
        if channels else _head_decay(q, k, beta, rows(g)[..., 0], mm)
    Tm = _inv_unit_lower(A) * beta[..., None, :]
    W = mm(Tm, gamma * k)
    U = mm(Tm, v)
    Qg = gamma * q
    KhT = jnp.swapaxes(Kh, -1, -2)

    def body(S, xs):
        W, U, M, Qg, KhT, gC = xs
        u = U - mm(W, S)
        o = mm(Qg, S) + mm(M, u)
        return gC * S + mm(KhT, u), o
    S, o = jax.lax.scan(body, jnp.zeros((b, H, dk, dv), _F32),
                        (W, U, M, Qg, KhT, gC))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, n * C, H, dv)
    return o[:, :T], S


def delta_rule_step(S, q, k, v, beta, decay):
    """The recurrence itself, one token a slot, in float32 on the VPU (a
    matmul would round the state to bfloat16 on its way in): ``S (..., H,
    dk, dv)``, ``q, k (..., H, dk)``, ``v (..., H, dv)``, ``beta (...,
    H)`` and ``decay = exp(g)`` as it meets the state: ``(..., H, 1, 1)``
    for one a head, ``(..., H, dk, 1)`` for one a channel.  Returns ``(S_t,
    o_t (..., H, dv))``."""
    S = decay * S
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.sum(S * q[..., None], axis=-2)


# -- the step against a pool of states, in place --------------------------

def _state_step_plain(pool, q, k, v, beta, decay, active, *, li):
    """:func:`delta_state_step` as ``jax.numpy``: the recurrence over
    layer ``li``'s states, written back under ``active``."""
    S, o = delta_rule_step(pool[li], q, k, v, beta, decay[..., None])
    keep = active[:, None, None, None]
    return pool.at[li].set(jnp.where(keep, S, pool[li])), o


def _state_kernel(_li_ref, kT_ref, qT_ref, aT_ref, v_ref, b_ref, s_ref,
                  so_ref, o_ref):
    """One place of the grid: ONE slot's states of one layer, every head's
    ``(dk, dv)`` matrix read once into VMEM, decayed, corrected, read and
    written back.  ``kT, qT, aT (dk, H)`` hold a head's key, query and
    decay as a COLUMN (a channel a sublane, as the state's rows lie), ``v,
    b (H, dv)`` its value and ``beta`` as rows."""
    H = s_ref.shape[0]
    for h in range(H):
        col = lambda ref: ref[:, h:h + 1]                    # (dk, 1)
        S = col(aT_ref) * s_ref[h]                           # (dk, dv)
        kc = col(kT_ref)
        u = b_ref[h:h + 1, :] * (
            v_ref[h:h + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True))
        S = S + kc * u
        so_ref[h] = S
        o_ref[h:h + 1, :] = jnp.sum(S * col(qT_ref), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_call(li, pool, q, k, v, beta, decay, active, *, interpret):
    """The kernel's call: the grid walks the slots; the index maps name
    slot ``s`` of layer ``li[0]`` in the stacked pool, which goes in whole
    and comes back ALIASED (no layer is sliced out or copied; the other
    layers' states are not touched), and the pipeline copies the next
    slot's 2 MB while this one computes.  A slot that is not ``active``
    gets a key of 0 and a decay of 1: its state comes back as it was, bit
    for bit.  A jit of its own with the pool as an argument: every KDA
    layer of a step is then the same computation, traced and lowered to
    Mosaic once a program (see ``nn/conf/attention.py:_pages_call``)."""
    L, S, H, dk, dv = pool.shape
    live = active[:, None, None]
    cols = lambda a: jnp.swapaxes(a, 1, 2)                   # (S, dk, H)
    rows = lambda a: jnp.broadcast_to(a, (S, H, dv)).astype(_F32)
    col_spec = pl.BlockSpec((None, dk, H), lambda s, li: (s, s * 0, s * 0))
    row_spec = pl.BlockSpec((None, H, dv), lambda s, li: (s, s * 0, s * 0))
    pool_spec = pl.BlockSpec(
        (None, None, H, dk, dv),
        lambda s, li: (li[0], s, s * 0, s * 0, s * 0))
    return pl.pallas_call(
        _state_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[col_spec, col_spec, col_spec, row_spec, row_spec,
                      pool_spec],
            out_specs=[pool_spec, row_spec]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, H, dv), _F32)],
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        name="kda_step",
        interpret=interpret,
    )(li, cols(jnp.where(live, k, _F32(0))), cols(q),
      cols(jnp.where(live, decay, _F32(1))), v, rows(beta[..., None]), pool)


def _state_step_kernel(pool, q, k, v, beta, decay, active, *, li,
                       interpret=False):
    return tuple(_state_call(jnp.full((1,), li, jnp.int32), pool, q, k, v,
                             beta, decay, active, interpret=interpret))


def _state_step_lowering(ctx, *args, li):
    form = _state_step_kernel if lowered_for_one_tpu(ctx) \
        else _state_step_plain
    return mlir.lower_fun(functools.partial(form, li=li),
                          multiple_results=True)(ctx, *args)


_state_step_p = jex_core.Primitive("delta_state_step")
_state_step_p.multiple_results = True


@functools.partial(jax.jit, static_argnames=("li",))
def _state_step_eager(*args, li):
    """Outside any jit the primitive runs as a program of its own."""
    return _state_step_p.bind(*args, li=li)


_state_step_p.def_impl(_state_step_eager)
_state_step_p.def_abstract_eval(
    lambda pool, q, k, v, beta, decay, active, *, li: (
        jax.core.ShapedArray(pool.shape, pool.dtype),
        jax.core.ShapedArray(v.shape, jnp.float32)))
mlir.register_lowering(_state_step_p, _state_step_lowering)


def delta_state_step(pool, li: int, q, k, v, beta, decay, active):
    """:func:`delta_rule_step` against layer ``li`` of a POOL of states,
    in place, where the state's bytes are the time: ``pool (layers,
    slots, H, dk, dv)`` float32, one token a slot, ``decay (slots, H,
    dk)`` one a channel, ``active (slots,)`` bool: the state of a slot
    that is not active comes back as it was.  Returns ``(pool, o (slots,
    H, dv))``.  Chosen by what the program is lowered for, not by a knob
    (the rule of ``paged_attention``): one TPU -> the kernel that reads
    every state once and writes it once (as ``jax.numpy`` the compiler
    reads it three times: for ``S'^T k``, for ``S^T q`` and for the
    write); the CPU or several devices -> the recurrence as it is
    written."""
    return _state_step_p.bind(pool, q, k, v, beta, decay, active, li=li)
