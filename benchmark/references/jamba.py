"""Plain reference for the ``jamba`` family: AI21-Jamba2-3B, Mamba-1
layers (Gu and Dao, arXiv:2312.00752) with one attention layer to every
thirteen of them, in the block of Jamba (Lieber et al., arXiv:2403.19887).
The full causal forward pass in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision, one sequence at a time, no cache, no kernel,
no batching, one layer at a time and the softmax over blocks of 512
queries so that 4,096 positions fit beside the weights; the state-space
recurrence is the plain sequential ``lax.scan`` below, whatever form the
program runs.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves) and the family's
builder (``configs/jamba.py``) hands the same arrays to the program.

The equations.  ``d`` 2560, ``H`` 20 query heads on ``KV`` 1 head of
``dh`` 128, ``ff`` 8192, ``d_in`` 5120, ``N`` 16, ``K`` 4, ``R`` 160;
RMSNorm ``n(x; g) = x * rsqrt(mean(x^2) + 1e-6) * g``; ``x`` a ``(T, d)``
sequence.  Layer ``i`` of 28 is *attention* where ``i % 14 == 7`` (layers
7 and 21), else *mamba*.  Every layer::

    h  = x + mixer(n(x; g1))
    x' = h + W_down(silu(W_gate u) * (W_up u)),  u = n(h; g2)

(``num_experts`` is 1: every FFN is this dense one and there is no
router.)

*mamba* on ``v``::

    [xs | z] = v W_in
    c_t = silu(b_conv + sum_{k<4} w_k * xs_{t-3+k})   depthwise, causal,
                                                      zeros before t = 0
    [δ | B | C] = c_t W_x
    δ' = n(δ; g_dt), B' = n(B; g_B), C' = n(C; g_C)   the three inner norms
    Δ = softplus(δ' W_dt + b_dt);  A = -exp(A_log)    (d_in, N)
    s_t = exp(Δ_t A) * s_{t-1} + (Δ_t c_t) B'_t^T;  y_t = s_t C'_t + D * c_t
    out = (y_t * silu(z_t)) W_out

*attention* on ``v``: ``q = v W_q`` (20 heads of 128), ``k = v W_k``,
``v = v W_v`` (ONE head of 128 each), causal softmax of ``q k^T /
sqrt(128)``, every query head on the one KV head, ``W_o``; no bias, no
rotary or any other position, no window.

``logits = n(x_L; g_f) E^T`` with the tied table ``E``.

What ``config.json`` does not carry is listed in the configuration's file
under ``assumed``.  No departure from the equations above is made here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STD = 0.02
HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
QUERY_BLOCK = 512


def dims(config: dict) -> dict:
    d, H = config["hidden_size"], config["num_attention_heads"]
    if config["num_experts"] != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] \
            or not config["tie_word_embeddings"] \
            or config["sliding_window"] is not None:
        raise ValueError("this reference writes the published Jamba2-3B "
                         "block: one dense expert, a convolution bias and "
                         "no projection bias, a tied head, no window")
    return {"d": d, "H": H, "KV": config["num_key_value_heads"],
            "dh": d // H, "ff": config["intermediate_size"],
            "d_in": config["mamba_expand"] * d,
            "N": config["mamba_d_state"], "K": config["mamba_d_conv"],
            "R": config["mamba_dt_rank"], "L": config["num_hidden_layers"],
            "V": config["vocab_size"], "eps": config["rms_norm_eps"]}


def layer_kinds(config: dict) -> list:
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(config["num_hidden_layers"])]


# -- weights ---------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "dm"))
def _make_layer(key, kind: str, dm: tuple):
    d, H, KV, dh, ff, d_in, N, K, R = dm
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 16))

    def normal(shape):
        return (STD * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(bf)

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(bf)

    ones = lambda n: jnp.ones((n,), bf)
    p = {"norm_1": ones(d), "norm_2": ones(d),
         "mlp": {"w_gate": normal((d, ff)), "w_up": normal((d, ff)),
                 "w_down": normal((ff, d))}}
    if kind == "mamba":
        # Mamba's own initialisation: A = -(1..N) on every channel, D = 1,
        # the dt bias such that softplus(b_dt) is log-uniform in
        # [1e-3, 1e-1], the dt projection uniform in +-R^-0.5, the
        # depthwise convolution as torch draws it (+-K^-0.5)
        dt = jnp.exp(jax.random.uniform(next(keys), (d_in,), jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        p["mixer"] = {
            "w_in": normal((d, 2 * d_in)),
            "conv_w": uniform((K, d_in), K ** -0.5),
            "conv_b": uniform((d_in,), K ** -0.5),
            "w_x": normal((d_in, R + 2 * N)),
            "w_dt": uniform((R, d_in), R ** -0.5),
            "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (d_in, N)).astype(bf),
            "d_skip": ones(d_in), "w_out": normal((d_in, d)),
            "dt_norm": ones(R), "b_norm": ones(N), "c_norm": ones(N)}
    else:
        p["mixer"] = {"w_q": normal((d, H * dh)), "w_k": normal((d, KV * dh)),
                      "w_v": normal((d, KV * dh)), "w_o": normal((H * dh, d))}
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    bf = jnp.bfloat16
    return ((STD * jax.random.normal(key, (V, d), jnp.float32)).astype(bf),
            jnp.ones((d,), bf))


def make_weights(config: dict, key):
    """Seeded bfloat16 weights, made on the device, one small jitted
    program per kind of layer."""
    D = dims(config)
    dm = tuple(D[n] for n in ("d", "H", "KV", "dh", "ff", "d_in", "N", "K",
                              "R"))
    emb, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, i + 1), kind, dm)
              for i, kind in enumerate(layer_kinds(config))]
    return {"emb": emb, "norm_f": norm_f, "layers": layers}


# -- the forward pass -------------------------------------------------------
def _mm(a, w, low: bool):
    """A matmul with a weight, float32 at ``highest``.  ``low`` is the
    control: its input and its weight are rounded to float8 (e4m3)."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if low:
        a, w = a.astype(F8).astype(jnp.float32), w.astype(F8).astype(
            jnp.float32)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def selective_scan(Delta, c, B, C, A):
    """The recurrence itself, position by position, from a zero state:
    ``Delta, c (T, d_in)``, ``B, C (T, N)``, ``A (d_in, N)``; returns ``(y
    (T, d_in)`` before the ``D`` skip, ``s_T (d_in, N))``."""
    def step(s, t):
        Dt, ct, Bt, Ct = t
        s = jnp.exp(Dt[:, None] * A) * s + (Dt * ct)[:, None] * Bt[None, :]
        return s, jnp.sum(s * Ct[None, :], axis=-1)
    s, y = lax.scan(step, jnp.zeros(A.shape, jnp.float32), (Delta, c, B, C))
    return y, s


def mamba(h, p, D, low, norms: bool = True):
    """The Mamba mixer over one sequence ``h (T, d)``; ``norms`` False
    leaves the three inner norms out (the mixer SambaY has)."""
    f32 = lambda a: a.astype(jnp.float32)
    T = h.shape[0]
    d_in, N, K, R = D["d_in"], D["N"], D["K"], D["R"]
    xz = _mm(h, p["w_in"], low)
    xs, z = xz[:, :d_in], xz[:, d_in:]
    xp = jnp.concatenate([jnp.zeros((K - 1, d_in), jnp.float32), xs])
    c = jax.nn.silu(sum(f32(p["conv_w"])[k] * xp[k:k + T] for k in range(K))
                    + f32(p["conv_b"]))
    dbc = _mm(c, p["w_x"], low)
    delta, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if norms:
        delta, B, C = (_rms(delta, p["dt_norm"], D["eps"]),
                       _rms(B, p["b_norm"], D["eps"]),
                       _rms(C, p["c_norm"], D["eps"]))
    Delta = jax.nn.softplus(_mm(delta, p["w_dt"], low) + f32(p["b_dt"]))
    y, _ = selective_scan(Delta, c, B, C, -jnp.exp(f32(p["a_log"])))
    y = y + f32(p["d_skip"]) * c
    return _mm(y * jax.nn.silu(z), p["w_out"], low)


def _attention(h, p, D, low):
    """Causal softmax attention of one sequence, ``H / KV`` query heads on
    each KV head, a block of queries at a time against every key."""
    T = h.shape[0]
    H, KV, dh = D["H"], D["KV"], D["dh"]
    q = _mm(h, p["w_q"], low).reshape(T, KV, H // KV, dh)
    k = _mm(h, p["w_k"], low).reshape(T, KV, dh)
    v = _mm(h, p["w_v"], low).reshape(T, KV, dh)
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * B, B)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HI) / math.sqrt(dh)
        rows = i * B + jnp.arange(B)[:, None]
        a = jax.nn.softmax(jnp.where(keys <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, H * dh)
    return _mm(o, p["w_o"], low)


@functools.partial(jax.jit, static_argnames=("kind", "Dt", "low"))
def _layer(x, p, kind: str, Dt: tuple, low: bool):
    """One layer over one sequence ``x (T, d)`` in float32."""
    D = dict(Dt)
    v = _rms(x, p["norm_1"], D["eps"])
    m = p["mixer"]
    h = x + (mamba(v, m, D, low) if kind == "mamba"
             else _attention(v, m, D, low))
    u = _rms(h, p["norm_2"], D["eps"])
    f = p["mlp"]
    return h + _mm(jax.nn.silu(_mm(u, f["w_gate"], low))
                   * _mm(u, f["w_up"], low), f["w_down"], low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, emb, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), emb.T, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    Dt = tuple(sorted(dims(config).items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for kind, p in zip(layer_kinds(config), weights["layers"]):
        x = _layer(x, p, kind, Dt, low)
    return x


def pad_to(tokens: list, multiple: int = QUERY_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: every mixer is causal."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["emb"],
                 config["rms_norm_eps"], low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _gaps(x, x_low, norm_f, emb, nxt, eps: float, low: bool):
    """Per position: how far the reference's logit of the token ``nxt``
    (the one that followed) lies below the reference's best, and the same
    for the token that the control's hidden state ``x_low`` puts first."""
    ref = _head(x, norm_f, emb, eps, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    ctl = _head(x_low, norm_f, emb, eps, low)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(ctl, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False) -> dict:
    """For one finished request, served by prefill and then decoding
    through the cache: at every position that produced a served token, how
    far the served token's logit in this full forward lies below its best.
    With ``control``, also the same gap for the token that the float8
    computation puts first at that position (teacher forced on the same
    prompt and tokens; it need not decode).  The sequence is padded to a
    multiple of 512 (4,096 at most in the cell) and the served positions
    go through the head in a slab of whole 512s, so a handful of programs
    serve every request."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    slab = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    at = min(first, len(padded) - slab)
    nxt = jnp.asarray(pad_to(seq[1:])[at:at + slab], jnp.int32)
    x = hidden(config, weights, padded)[at:at + slab]
    x_low = hidden(config, weights, padded, True)[at:at + slab] \
        if control else x
    got, low = _gaps(x, x_low, weights["norm_f"], weights["emb"], nxt,
                     config["rms_norm_eps"], control)
    where = slice(first - at, first - at + n)
    out = {"served": np.asarray(got)[where].tolist()}
    if control:
        out["control"] = np.asarray(low)[where].tolist()
    return out


# -- counts for the rooflines ------------------------------------------------
def layer_params(config: dict) -> dict:
    """Parameters of one layer of each kind, its two norms among them."""
    D = dims(config)
    d, ff, d_in, N, K, R = (D[n] for n in ("d", "ff", "d_in", "N", "K", "R"))
    Hd, KVd = D["H"] * D["dh"], D["KV"] * D["dh"]
    common = 2 * d + 3 * d * ff
    mixer = 2 * d * d_in + (K + 1) * d_in + d_in * (R + 2 * N) \
        + (R + 1) * d_in + d_in * N + d_in + d_in * d + R + 2 * N
    return {"mamba": common + mixer,
            "attention": common + 2 * d * Hd + 2 * d * KVd}


def param_count(config: dict) -> int:
    D = dims(config)
    per = layer_params(config)
    return D["V"] * D["d"] + D["d"] + sum(per[k] for k in layer_kinds(config))


def param_bytes(config: dict, itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: every layer's
    matrices and the tied table as the output head."""
    return float(itemsize * param_count(config))


def cache_bytes(config: dict) -> dict:
    """Bytes of each kind of state that the layers keep between steps:
    ``paged`` per live position (the key and value rows of both attention
    layers, bfloat16, each read by its own layer alone), ``recurrent`` per
    live slot (every Mamba layer's float32 ``(N, d_in)`` state and the
    convolution's ``K - 1`` bfloat16 rows)."""
    D = dims(config)
    kinds = layer_kinds(config)
    return {"paged": float(kinds.count("attention") * 2 * D["KV"] * D["dh"]
                           * 2),
            "recurrent": float(kinds.count("mamba") * D["d_in"]
                               * (4 * D["N"] + 2 * (D["K"] - 1)))}


def decode_step_bytes(config: dict, live_positions: float,
                      live_slots: float = 0.0) -> float:
    """Bytes one decode step needs to move: the weights once, the paged
    rows of the live positions, and the state and convolution windows of
    the live slots read and written.  The step is bound by bytes (64 rows
    against 3 B weights).  With only ``live_positions`` given it is the
    count the gpt2 family makes, weights and live keys and values: a
    floor under the step's bytes, so a share taken of it never passes
    100."""
    c = cache_bytes(config)
    return param_bytes(config) + live_positions * c["paged"] \
        + 2.0 * live_slots * c["recurrent"]


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight of every layer's matrices at every position (the table is
    gathered, not multiplied); the convolution's ``2 K`` a channel; the
    causal half of the scores and of the context in the attention layers;
    the recurrence as it counts itself, 9 a state element a position
    (``Δ A``, the exponential taken as three, the decay, ``(Δ c) B``, the
    sum, and the read-out's product and sum), whatever form the program
    computes it in; the head at the last position alone."""
    D = dims(config)
    d, ff, d_in, N, K, R = (D[n] for n in ("d", "ff", "d_in", "N", "K", "R"))
    kinds = layer_kinds(config)
    nM, nA = kinds.count("mamba"), kinds.count("attention")
    Hd, KVd = D["H"] * D["dh"], D["KV"] * D["dh"]
    matrices = (nM + nA) * 3 * d * ff \
        + nM * (2 * d * d_in + d_in * (R + 2 * N) + R * d_in + d_in * d) \
        + nA * (2 * d * Hd + 2 * d * KVd)
    return 2.0 * matrices * t + nM * 2.0 * K * d_in * t \
        + nA * 2.0 * t * t * Hd + nM * 9.0 * N * d_in * t + 2.0 * d * D["V"]
