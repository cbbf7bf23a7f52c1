"""The state-space mixers of the serving tier, each in the two forms a
served model needs: over whole left-padded sequences (forward and
prefill) and one token a slot against the state the scheduler's pool
keeps (the decode step).

**Mamba-1** (selective state space, arXiv:2312.00752): ``mamba_full``,
``mamba_step``, ``selective_scan``.  ``SambaYLM`` (9 of its 32 layers)
and ``JambaLM`` (26 of 28) call it; Jamba's differs in one thing, an
RMSNorm on each of ``Δ``'s ``R`` inputs, ``B`` and ``C``, which ``eps``
turns on.  On ``h`` (the block's normed input), with ``d_in = expand *
d``, state size ``N``, ``K`` taps and ``Δ`` rank ``R``::

    [xs | z] = h W_in
    c_t = silu(b_conv + sum_k w_k * xs_{t-K+1+k})      zeros before the
                                                       first real position
    [δ | B | C] = c_t W_x        (δ, B, C through their RMSNorms if ``eps``)
    Δ = softplus(δ W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(Δ_t A) * s_{t-1} + (Δ_t c_t) B_t^T;  y_t = s_t C_t + D * c_t
    out = (y_t * silu(z_t)) W_out

A layer's parameters are a dict: ``Win (d, 2 d_in)``, ``convW (K, d_in)``,
``convB``, ``Wx (d_in, R + 2N)``, ``Wdt (R, d_in)``, ``bdt``, ``AlogT (N,
d_in)`` (kept as the state is kept), ``D``, ``Wout (d_in, d)`` and, with
``eps``, the gains ``dtNorm (R,)``, ``bNorm (N,)``, ``cNorm (N,)``.

**Mamba-2** (state-space duality, SSD, arXiv:2405.21060): ``ssd_full``,
``ssd_step``, ``ssd_chunked``.  ``NemotronHLM`` calls it (5 of the 11
blocks it holds).  ONE decay a head (a scalar ``A_h``), ``B`` and ``C``
of ``G`` groups shared by ``H / G`` heads each, a matrix state ``(P, N)``
a head; ``H`` heads of ``P``, ``d_in = H P``::

    [z | xBC | dt] = h W_in          xBC (d_in + 2 G N) wide, dt (H)
    xBC_t = silu(b_conv + sum_k w_k * xBC_{t-K+1+k})
    xBC = [x (H, P) | B (G, N) | C (G, N)];  dt = softplus(dt + dt_bias)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    out = RMSNorm_group(y * silu(z)) W_out     the gate FIRST, then an RMS
                                               norm over each of G groups

The step's pass over the state is :func:`ssd_state_step`: lowered for
one TPU a kernel that reads a slot's states once and writes them once in
place, elsewhere the recurrence as it is written.  Its prefill is the
chunked form: inside a chunk of ``Q`` positions ``Y =
((C B^T) ∘ L)(dt x)`` with ``L_ij = exp(g_i - g_j)`` for ``i >= j``, ``g``
the running sum of ``dt A`` (never a positive exponent: the decay is one
scalar a head), between chunks a recurrence over the ``(H, P, N)``
states.  Parameters: ``Win (d, 2 d_in + 2 G N + H)``, ``convW (K, d_in +
2 G N)``, ``convB``, ``dtBias (H,)``, ``Alog (H,)``, ``D (H,)``, ``gnorm
(d_in,)``, ``Wout (d_in, d)``.

Precision, both: matmuls take their input in the weight's dtype and
accumulate in float32; the state, ``Δ``, ``exp``, the convolution and the
inner norms are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend import core as jex_core
from jax.interpreters import mlir

from deeplearning4j_tpu.nn.conf.attention import lowered_for_one_tpu

__all__ = ["mamba_full", "mamba_step", "selective_scan", "ssd_full",
           "ssd_step", "ssd_chunked", "ssd_state_step",
           "ssd_step_kernel_lowerings"]

_F32 = jnp.float32


def _mm(a, w):
    """``a @ w`` in the weight's dtype on the way in, float32 out."""
    return jnp.matmul(a.astype(w.dtype), w, preferred_element_type=_F32)


def _rms(x, g, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(_F32)


def _conv_full(lp, u, K: int):
    """The causal depthwise convolution and its SiLU over ``u (b, T, c)``
    float32, zero at the pad positions: ``(silu(conv) (b, T, c), the last
    K - 1 rows of u)``; zeros stand before the first position."""
    b, T, c = u.shape
    tail = u[:, T - (K - 1):]
    up = jnp.concatenate([jnp.zeros((b, K - 1, c), _F32), u], axis=1)
    cw = lp["convW"].astype(_F32)
    return jax.nn.silu(sum(cw[k] * up[:, k:k + T] for k in range(K))
                       + lp["convB"].astype(_F32)), tail


def _conv_step(lp, u, win, keep):
    """The same on one new row a slot, ``u (S, c)`` behind the windows
    ``win (S, K - 1, c)``: ``(silu(conv) (S, c), the windows moved on a
    row where keep says so)``."""
    w = jnp.concatenate([win.astype(_F32), u[:, None]], axis=1)  # (S, K, c)
    newWin = keep(w[:, 1:].astype(win.dtype), win)
    return jax.nn.silu(jnp.sum(w * lp["convW"].astype(_F32)[None], axis=1)
                       + lp["convB"].astype(_F32)), newWin


def ssm_step(s, Dt, ut, Bt, Ct, AT):
    """One step of the selective scan over a batch: state ``s (b, N,
    d_in)`` float32, ``Dt, ut (b, d_in)``, ``Bt, Ct (b, N)``, ``AT (N,
    d_in)``; returns ``(s, y (b, d_in))`` before the ``D`` skip."""
    s = jnp.exp(Dt[:, None, :] * AT[None]) * s \
        + (Dt * ut)[:, None, :] * Bt[:, :, None]
    return s, jnp.sum(s * Ct[:, :, None], axis=1)


def ssm_inputs(lp, u, R: int, N: int, eps=None):
    """From the convolved ``u (..., d_in)`` float32: ``(Δ, B, C)``; with
    ``eps`` each of ``δ``, ``B``, ``C`` goes through its RMSNorm first."""
    dbc = _mm(u, lp["Wx"])
    d, B, C = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    if eps is not None:
        d, B, C = (_rms(d, lp["dtNorm"], eps), _rms(B, lp["bNorm"], eps),
                   _rms(C, lp["cNorm"], eps))
    Dt = jax.nn.softplus(_mm(d, lp["Wdt"]) + lp["bdt"].astype(_F32))
    return Dt, B, C


def selective_scan(Dt, u, B, C, AT):
    """The recurrence over whole sequences from a zero state: ``Dt, u (b,
    T, d_in)``, ``B, C (b, T, N)``, ``AT (N, d_in)``, all float32 ->
    ``(y (b, T, d_in)`` before the ``D`` skip, ``s_T (b, N, d_in))``.
    Sequential in the positions; a position whose ``Dt`` is 0 leaves the
    state as it was."""
    def step(s, t):
        return ssm_step(s, *t, AT)
    tm = lambda a: jnp.swapaxes(a, 0, 1)                     # time-major
    s, y = jax.lax.scan(
        step, jnp.zeros((u.shape[0],) + AT.shape, _F32),
        (tm(Dt), tm(u), tm(B), tm(C)), unroll=8)
    return tm(y), s


def mamba_full(lp, h, realF, *, N: int, K: int, R: int, eps=None):
    """The mixer over ``h (b, T, d)`` LEFT-padded, ``realF (b, T, 1)`` 1.0
    at the real positions.  Returns ``(out (b, T, d) float32, y (b, T,
    d_in)`` after the ``D`` skip and before the ``z`` gate, the final
    state ``(b, N, d_in)`` and the convolution's last ``K - 1`` inputs
    ``(b, K - 1, d_in)`` float32).  A pad position advances nothing: its
    ``xs`` and its ``Δ`` are zero."""
    b, T, _ = h.shape
    xz = _mm(h, lp["Win"])
    dIn = xz.shape[-1] // 2
    z = xz[..., dIn:]
    u, tail = _conv_full(lp, xz[..., :dIn] * realF, K)
    Dt, B, C = ssm_inputs(lp, u, R, N, eps)
    Dt = Dt * realF
    y, s = selective_scan(Dt, u, B, C, -jnp.exp(lp["AlogT"].astype(_F32)))
    y = y + lp["D"].astype(_F32) * u
    return _mm(y * jax.nn.silu(z), lp["Wout"]), y, s, tail


def mamba_step(lp, h, s, win, keep, *, N: int, R: int, eps=None):
    """The mixer on one token a slot: ``h (S, d)``, the slots' state ``s
    (S, N, d_in)`` float32 and convolution windows ``win (S, K - 1,
    d_in)``.  Returns ``(out (S, d) float32, y (S, d_in), s', win')``;
    ``keep(new, old)`` says slot by slot whose state advances (a slot
    that holds no sequence keeps what it has)."""
    xz = _mm(h, lp["Win"])
    dIn = xz.shape[-1] // 2
    u, z = xz[:, :dIn], xz[:, dIn:]
    u, newWin = _conv_step(lp, u, win, keep)
    Dt, B, C = ssm_inputs(lp, u, R, N, eps)
    sNew, y = ssm_step(s, Dt, u, B, C, -jnp.exp(lp["AlogT"].astype(_F32)))
    y = y + lp["D"].astype(_F32) * u
    return _mm(y * jax.nn.silu(z), lp["Wout"]), y, keep(sNew, s), newWin


# -- Mamba-2 (SSD) --------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, chunk: int, dtype=_F32):
    """The SSD recurrence from a zero state in chunks of ``chunk``
    positions: ``x (b, T, H, P)``, ``dt (b, T, H)`` (0 at a position that
    advances nothing), ``A (H,)`` negative, ``B, C (b, T, G, N)``, all
    float32 -> ``(y (b, T, H, P)`` before the ``D`` skip, ``S_T (b, H, P,
    N))`` float32.  Head ``h`` reads group ``h // (H / G)``.  Inside a
    chunk two matmuls (``C B^T`` a group, then the masked, decayed map
    against ``dt x`` a head); a chunk's end state is one more, and the
    states are carried from chunk to chunk by a scan; what the chunks
    before add to a position is its ``C`` against their state.  Every
    exponent is a difference ``g_i - g_j`` with ``i >= j`` of running sums
    of ``dt A <= 0``.  The matmuls take their inputs in ``dtype`` and sum
    in float32; a length that is no multiple of ``chunk`` is padded on the
    LEFT with positions that advance nothing."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    r, Q = H // G, chunk
    pad = -T % Q
    if pad:
        left = lambda a: jnp.pad(a, ((0, 0), (pad, 0)) + ((0, 0),)
                                 * (a.ndim - 2))
        x, dt, B, C = left(x), left(dt), left(B), left(C)
    nc = (T + pad) // Q
    mm = lambda eq, a, c: jnp.einsum(eq, a.astype(dtype), c.astype(dtype),
                                     preferred_element_type=_F32)
    # chunks leading, then groups and their heads, positions last
    g = jnp.cumsum((dt * A).reshape(b, nc, Q, G, r).transpose(0, 1, 3, 4, 2),
                   axis=-1)                                  # (b, nc, G, r, Q)
    Bc = B.reshape(b, nc, Q, G, N).transpose(0, 1, 3, 2, 4)  # (b, nc, G, Q, N)
    Cc = C.reshape(b, nc, Q, G, N).transpose(0, 1, 3, 2, 4)
    xd = (x * dt[..., None]).reshape(b, nc, Q, G, r, P
                                     ).transpose(0, 1, 3, 4, 5, 2)
    at = jnp.arange(Q)
    L = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                          g[..., :, None] - g[..., None, :], -jnp.inf))
    M = mm("bcgin,bcgjn->bcgij", Cc, Bc)[:, :, :, None] * L  # (.., r, Q, Q)
    yIn = mm("bcgrij,bcgrpj->bcgrip", M, xd)                 # (.., r, Q, P)
    # a chunk's own end state, each position decayed to the chunk's end
    Sl = mm("bcgrpj,bcgnj->bcgrpn",
            xd * jnp.exp(g[..., -1:] - g)[..., None, :],
            Bc.swapaxes(-1, -2))                             # (.., r, P, N)

    def carry(S, t):
        decay, own = t
        return decay[..., None, None] * S + own, S
    first = lambda a: jnp.moveaxis(a, 1, 0)
    S, before = jax.lax.scan(carry, jnp.zeros((b, G, r, P, N), _F32),
                             (first(jnp.exp(g[..., -1])), first(Sl)))
    yOut = mm("bcgin,bcgmn->bcgim", Cc,
              jnp.moveaxis(before, 0, 1).reshape(b, nc, G, r * P, N)
              ).reshape(b, nc, G, Q, r, P)
    y = yIn.transpose(0, 1, 4, 2, 3, 5) + yOut.transpose(0, 1, 3, 2, 4, 5) \
        * jnp.exp(g).transpose(0, 1, 4, 2, 3)[..., None]     # (b, nc, Q, G, r, P)
    return y.reshape(b, T + pad, H, P)[:, pad:], S.reshape(b, H, P, N)


def _ssd_split(lp, zxd, H: int, P: int, G: int, N: int):
    """``(z, xBC, dt)`` of a position's projection ``(.., 2 d_in + 2 G N
    + H)``, ``dt`` through its bias and softplus."""
    dIn, conv = H * P, H * P + 2 * G * N
    return zxd[..., :dIn], zxd[..., dIn:dIn + conv], jax.nn.softplus(
        zxd[..., dIn + conv:] + lp["dtBias"].astype(_F32))


def _ssd_out(lp, y, z, G: int, eps: float):
    """``RMSNorm_group(y * silu(z)) W_out``: the gate first, then the norm
    over each of the ``G`` groups of ``d_in / G`` channels."""
    y = y * jax.nn.silu(z)
    grouped = y.reshape(y.shape[:-1] + (G, -1))
    y = (grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
         ).reshape(y.shape) * lp["gnorm"].astype(_F32)
    return _mm(y, lp["Wout"])


def ssd_full(lp, h, realF, *, H: int, P: int, G: int, N: int, K: int,
             chunk: int, eps: float):
    """The Mamba-2 mixer over ``h (b, T, d)`` LEFT-padded, ``realF (b, T,
    1)`` 1.0 at the real positions.  Returns ``(out (b, T, d) float32,
    the state at the last position (b, H, P, N) float32, the
    convolution's last K - 1 inputs (b, K - 1, d_in + 2 G N) float32)``.
    A pad position advances nothing: its ``xBC`` and its ``dt`` are zero.
    The chunks' work carries the scope ``ssd_prefill``."""
    b, T, _ = h.shape
    dIn, gn = H * P, G * N
    z, xBC, dt = _ssd_split(lp, _mm(h, lp["Win"]), H, P, G, N)
    u, tail = _conv_full(lp, xBC * realF, K)
    x = u[..., :dIn].reshape(b, T, H, P)
    with jax.named_scope("ssd_prefill"):
        y, S = ssd_chunked(
            x, dt * realF, -jnp.exp(lp["Alog"].astype(_F32)),
            u[..., dIn:dIn + gn].reshape(b, T, G, N),
            u[..., dIn + gn:].reshape(b, T, G, N), chunk, lp["Win"].dtype)
    y = y + lp["D"].astype(_F32)[:, None] * x
    return _ssd_out(lp, y.reshape(b, T, dIn), z, G, eps), S, tail


# -- the step's pass over a pool of states, in place ------------------------

def _ssd_state_plain(pool, decay, xd, B, C, active, *, li):
    """:func:`ssd_state_step` as ``jax.numpy``: ``S' = decay S + xd B^T``
    over layer ``li``'s states, ``y = S' C``, written back under
    ``active``."""
    L, S, H, P, N = pool.shape
    G = B.shape[1]
    old = pool[li].reshape(S, G, H // G, P, N)
    new = decay.reshape(S, G, -1, 1, 1) * old \
        + xd.reshape(S, G, -1, P, 1) * B[:, :, None, None, :]
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1)
    keep = active[:, None, None, None, None]
    return pool.at[li].set(jnp.where(keep, new, old).reshape(S, H, P, N)), \
        y.reshape(S, H, P)


def _ssd_state_kernel(_li_ref, aT_ref, xT_ref, b_ref, c_ref, s_ref, so_ref,
                      oT_ref):
    """One place of the grid: ONE slot's states of one layer, every
    head's ``(P, N)`` matrix read once into VMEM, decayed, added to, read
    against ``C`` and written back.  ``aT, xT (P, H)`` hold a head's
    decay and its ``dt x`` as a COLUMN (a channel a sublane, as the
    state's rows lie), ``b, c (G, N)`` a group's ``B`` and ``C`` as rows;
    the head's output leaves as a column of ``oT (P, H)``."""
    H = s_ref.shape[0]
    r = H // b_ref.shape[0]
    for h in range(H):
        g = h // r
        S = aT_ref[:, h:h + 1] * s_ref[h] \
            + xT_ref[:, h:h + 1] * b_ref[g:g + 1, :]            # (P, N)
        so_ref[h] = S
        oT_ref[:, h:h + 1] = jnp.sum(S * c_ref[g:g + 1, :], axis=1,
                                     keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_state_call(li, pool, decay, xd, B, C, active, *, interpret):
    """The kernel's call: the grid walks the slots; the index maps name
    slot ``s`` of layer ``li[0]`` in the stacked pool, which goes in whole
    and comes back ALIASED (no layer is sliced out or copied; the other
    layers' states are not touched), and the pipeline copies the next
    slot's 4 MB while this one computes.  A slot that is not ``active``
    gets a decay of 1 and a ``dt x`` of 0: its state comes back as it
    was, bit for bit.  A jit of its own with the pool as an argument:
    every Mamba-2 layer of a step is then the same computation, traced
    and lowered to Mosaic once a program (see
    ``nn/conf/attention.py:_pages_call``)."""
    L, S, H, P, N = pool.shape
    G = B.shape[1]
    live = active[:, None, None]
    # a head's scalar decay down its column, a head a lane
    aT = jnp.broadcast_to(jnp.where(live, decay[:, None, :], _F32(1)),
                          (S, P, H))
    xT = jnp.where(live, jnp.swapaxes(xd, 1, 2), _F32(0))    # (S, P, H)
    col_spec = pl.BlockSpec((None, P, H), lambda s, li: (s, s * 0, s * 0))
    row_spec = pl.BlockSpec((None, G, N), lambda s, li: (s, s * 0, s * 0))
    pool_spec = pl.BlockSpec(
        (None, None, H, P, N),
        lambda s, li: (li[0], s, s * 0, s * 0, s * 0))
    pool, oT = pl.pallas_call(
        _ssd_state_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[col_spec, col_spec, row_spec, row_spec, pool_spec],
            out_specs=[pool_spec, col_spec]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, P, H), _F32)],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20),
        name="ssd_step",
        interpret=interpret,
    )(li, aT, xT, B, C, pool)
    return pool, jnp.swapaxes(oT, 1, 2)


def _ssd_state_kernel_form(pool, decay, xd, B, C, active, *, li,
                           interpret=False):
    return tuple(_ssd_state_call(jnp.full((1,), li, jnp.int32), pool, decay,
                                 xd, B, C, active, interpret=interpret))


#: how often the step's pass was lowered as the kernel (program telemetry:
#: the batcher's gauge reads it around its warm-up)
_ssdKernelLowerings = [0]


def ssd_step_kernel_lowerings() -> int:
    """How many times :func:`ssd_state_step` has been lowered as the TPU
    kernel in this process (once a program built for one TPU, whose
    Mamba-2 layers of one shape share the lowering; never on the CPU or
    for several devices)."""
    return _ssdKernelLowerings[0]


def _ssd_state_lowering(ctx, *args, li):
    kernel = lowered_for_one_tpu(ctx)
    _ssdKernelLowerings[0] += kernel
    form = _ssd_state_kernel_form if kernel else _ssd_state_plain
    return mlir.lower_fun(functools.partial(form, li=li),
                          multiple_results=True)(ctx, *args)


_ssd_state_p = jex_core.Primitive("ssd_state_step")
_ssd_state_p.multiple_results = True


@functools.partial(jax.jit, static_argnames=("li",))
def _ssd_state_eager(*args, li):
    """Outside any jit the primitive runs as a program of its own."""
    return _ssd_state_p.bind(*args, li=li)


_ssd_state_p.def_impl(_ssd_state_eager)
_ssd_state_p.def_abstract_eval(
    lambda pool, decay, xd, B, C, active, *, li: (
        jax.core.ShapedArray(pool.shape, pool.dtype),
        jax.core.ShapedArray(xd.shape, jnp.float32)))
mlir.register_lowering(_ssd_state_p, _ssd_state_lowering)


def ssd_state_step(pool, li: int, decay, xd, B, C, active):
    """One step of the SSD recurrence against layer ``li`` of a POOL of
    states, in place, where the state's bytes are the time: ``pool
    (layers, slots, H, P, N)`` float32, one token a slot, ``decay (slots,
    H)`` = ``exp(dt A)``, ``xd (slots, H, P)`` = ``dt x``, ``B, C (slots,
    G, N)``, ``active (slots,)`` bool: the state of a slot that is not
    active comes back as it was.  Returns ``(pool, y (slots, H, P))``
    before the ``D`` skip.  Chosen by what the program is lowered for,
    not by a knob (the rule of ``paged_attention``): one TPU -> the kernel
    that reads every state once and writes it once (as ``jax.numpy`` the
    compiler reads it twice: a fusion for ``S' C``, another for the
    write); the CPU or several devices -> the recurrence as it is
    written."""
    return _ssd_state_p.bind(pool, decay, xd, B, C, active, li=li)


def ssd_step(lp, h, ssm, conv, li, active, *, H: int, P: int, G: int,
             N: int, eps: float):
    """The Mamba-2 mixer on one token a slot, ``h (S, d)``, against layer
    ``li`` of the pool's states ``ssm (layers, S, H, P, N)`` float32 and
    convolution windows ``conv (layers, S, K - 1, d_in + 2 G N)``.
    Returns ``(out (S, d) float32, ssm, conv)``; a slot that is not
    ``active (S,)`` keeps what it has.  The pass over the state
    (:func:`ssd_state_step`: decay, rank-one update, the read against
    ``C``, the write back into the pool) carries the scope ``ssd_step``."""
    S = h.shape[0]
    dIn, gn = H * P, G * N
    keep = lambda new, old: jnp.where(
        active.reshape((S,) + (1,) * (new.ndim - 1)), new, old)
    z, xBC, dt = _ssd_split(lp, _mm(h, lp["Win"]), H, P, G, N)
    u, win = _conv_step(lp, xBC, conv[li], keep)
    conv = conv.at[li].set(win)
    x = u[:, :dIn].reshape(S, H, P)
    with jax.named_scope("ssd_step"):
        ssm, y = ssd_state_step(
            ssm, li, jnp.exp(dt * -jnp.exp(lp["Alog"].astype(_F32))),
            dt[..., None] * x, u[:, dIn:dIn + gn].reshape(S, G, N),
            u[:, dIn + gn:].reshape(S, G, N), active)
    y = y + lp["D"].astype(_F32)[:, None] * x
    return _ssd_out(lp, y.reshape(S, dIn), z, G, eps), ssm, conv
