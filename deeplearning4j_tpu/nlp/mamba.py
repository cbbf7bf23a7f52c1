"""The Mamba-1 mixer (selective state space, arXiv:2312.00752) of the
serving tier, in the two forms a served model needs: over whole
left-padded sequences (forward and prefill) and one token a slot against
the state the scheduler's pool keeps (the decode step).  ``SambaYLM``
(9 of its 32 layers) and ``JambaLM`` (26 of 28) both call it; Jamba's
differs in one thing, an RMSNorm on each of ``Δ``'s ``R`` inputs, ``B``
and ``C``, which ``eps`` turns on.

On ``h`` (the block's normed input), with ``d_in = expand * d``, state
size ``N``, ``K`` taps and ``Δ`` rank ``R``::

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

Precision: matmuls take their input in the weight's dtype and accumulate
in float32; the state, ``Δ``, ``exp``, the convolution and the inner
norms are float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["mamba_full", "mamba_step", "selective_scan"]

_F32 = jnp.float32


def _mm(a, w):
    """``a @ w`` in the weight's dtype on the way in, float32 out."""
    return jnp.matmul(a.astype(w.dtype), w, preferred_element_type=_F32)


def _rms(x, g, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g.astype(_F32)


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
    u = xz[..., :dIn] * realF
    z = xz[..., dIn:]
    tail = u[:, T - (K - 1):]
    up = jnp.concatenate([jnp.zeros((b, K - 1, dIn), _F32), u], axis=1)
    cw = lp["convW"].astype(_F32)
    u = jax.nn.silu(sum(cw[k] * up[:, k:k + T] for k in range(K))
                    + lp["convB"].astype(_F32))
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
    w = jnp.concatenate([win.astype(_F32), u[:, None]], axis=1)  # (S, K, dIn)
    newWin = keep(w[:, 1:].astype(win.dtype), win)
    u = jax.nn.silu(jnp.sum(w * lp["convW"].astype(_F32)[None], axis=1)
                    + lp["convB"].astype(_F32))
    Dt, B, C = ssm_inputs(lp, u, R, N, eps)
    sNew, y = ssm_step(s, Dt, u, B, C, -jnp.exp(lp["AlogT"].astype(_F32)))
    y = y + lp["D"].astype(_F32) * u
    return _mm(y * jax.nn.silu(z), lp["Wout"]), y, keep(sNew, s), newWin
