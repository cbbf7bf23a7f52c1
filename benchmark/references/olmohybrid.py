"""Plain reference for the ``olmohybrid`` family: Olmo-Hybrid-7B, three
Gated-DeltaNet linear-attention layers (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) to every full-attention layer, in the Olmo 2/3 block
with its reordered norm (arXiv:2501.00656).  The full causal forward pass
in straightforward ``jax.numpy`` float32 at ``highest`` matmul precision,
one sequence at a time, no cache, no batching, one layer at a time and
the softmax over blocks of 512 queries so that 4,608 positions fit beside
the weights; **the delta rule is the token-by-token recurrence below**, a
sequential ``lax.scan``, so that the program's chunked form is compared
with the definition and not with itself.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves) and the family's
builder (``configs/olmohybrid.py``) hands the same arrays to the program.

The equations.  ``d`` 3840, ``H`` 30, ``dk`` 96, ``dv`` 192, ``K`` 4,
RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * g``, ``x`` a ``(T, d)`` sequence.

*Gated DeltaNet mixer* on ``h``::

    q = silu(conv_q(h Wq)), k = silu(conv_k(h Wk)), v = silu(conv_v(h Wv))
        (depthwise causal convolutions of K taps, no bias: tap K-1 meets
        the position itself, zeros before the sequence)
    per head: q <- q / sqrt(sum(q^2) + 1e-6) * dk^-1/2,
              k <- k / sqrt(sum(k^2) + 1e-6)
    beta  = 2 sigmoid(h Wb)                                  (T, H), in (0, 2)
    alpha = exp(-exp(A_log) softplus(h Wa + dt_bias))        (T, H), in (0, 1)
    per head, S in R^{dk x dv}, zero at the sequence's start:
        S' = alpha_t S_{t-1}
        u_t = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u_t^T
        o_t = S_t^T q_t
    out = (RMSNorm_dv(o) * g_norm * silu(h Wg)) Wo

(that is ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
v_t^T``; ``linear_allow_neg_eigval`` is what lets ``beta`` pass 1.)

*Full-attention mixer*: ``q = RMSNorm_d(h Wq)``, ``k = RMSNorm_d(h Wk)``
(over the whole width, before the split into heads), ``v = h Wv``, 30
heads of 128, causal softmax at ``128^-1/2``, ``Wo``; no bias anywhere and
no positional encoding of any kind.

*Block*, both kinds: ``y = x + RMSNorm(mixer(x))``, ``out = y +
RMSNorm(W_down(silu(y W_gate) * y W_up))``; a final RMSNorm; an untied
head.  Layer ``i`` is what ``layer_types[i]`` says: full attention where
``i % 4 == 3``.

What ``config.json`` does not carry is listed in the configuration's file
under ``assumed``.  The 1e-6 under the square root of the q/k
normalisation is the flash-linear-attention layer's; it is also what
leaves a zeroed row zero.
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
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("key and value heads of the linear layers differ: "
                         "the published model has 30 of each")
    return {"d": d, "H": H, "dh": d // H, "ff": config["intermediate_size"],
            "HL": config["linear_num_value_heads"],
            "dk": config["linear_key_head_dim"],
            "dv": config["linear_value_head_dim"],
            "K": config["linear_conv_kernel_dim"],
            "L": config["num_hidden_layers"], "V": config["vocab_size"],
            "eps": config["rms_norm_eps"]}


def layer_kinds(config: dict) -> list:
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return kinds


# -- weights ---------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "dm"))
def _make_layer(key, kind: str, dm: tuple):
    d, H, dh, ff, HL, dk, dv, K = dm
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 20))

    def normal(shape):
        return (STD * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(bf)

    def conv(width):       # as torch draws a depthwise Conv1d: +-K^-0.5
        return jax.random.uniform(next(keys), (K, width), jnp.float32,
                                  -K ** -0.5, K ** -0.5).astype(bf)

    ones = lambda n: jnp.ones((n,), bf)
    p = {"norm_1": ones(d), "norm_2": ones(d),
         "mlp": {"w_gate": normal((d, ff)), "w_up": normal((d, ff)),
                 "w_down": normal((ff, d))}}
    if kind == "linear_attention":
        # the flash-linear-attention layer's own initialisation: A uniform
        # in (0, 16), the dt bias such that softplus(dt_bias) is
        # log-uniform in [1e-3, 1e-1]
        A = jax.random.uniform(next(keys), (HL,), jnp.float32, 1e-4, 16.0)
        dt = jnp.exp(jax.random.uniform(next(keys), (HL,), jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        p["mixer"] = {
            "w_q": normal((d, HL * dk)), "w_k": normal((d, HL * dk)),
            "w_v": normal((d, HL * dv)), "w_g": normal((d, HL * dv)),
            "w_o": normal((HL * dv, d)),
            "w_a": normal((d, HL)), "w_b": normal((d, HL)),
            "conv_q": conv(HL * dk), "conv_k": conv(HL * dk),
            "conv_v": conv(HL * dv),
            "a_log": jnp.log(A).astype(bf),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
            "g_norm": ones(dv)}
    elif kind == "full_attention":
        p["mixer"] = {"w_q": normal((d, H * dh)), "w_k": normal((d, H * dh)),
                      "w_v": normal((d, H * dh)), "w_o": normal((H * dh, d)),
                      "q_norm": ones(H * dh), "k_norm": ones(H * dh)}
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    bf = jnp.bfloat16
    ke, kh = jax.random.split(key)
    emb = (STD * jax.random.normal(ke, (V, d), jnp.float32)).astype(bf)
    head = (STD * jax.random.normal(kh, (d, V), jnp.float32)).astype(bf)
    return emb, head, jnp.ones((d,), bf)


def make_weights(config: dict, key):
    """Seeded bfloat16 weights, made on the device, one small jitted
    program per kind of layer."""
    D = dims(config)
    dm = tuple(D[n] for n in ("d", "H", "dh", "ff", "HL", "dk", "dv", "K"))
    emb, head, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, i + 1), kind, dm)
              for i, kind in enumerate(layer_kinds(config))]
    return {"emb": emb, "head": head, "norm_f": norm_f, "layers": layers}


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


def _conv(x, w):
    """Depthwise causal convolution of ``x (T, c)`` with ``w (K, c)``."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), jnp.float32), x])
    w = w.astype(jnp.float32)
    return sum(w[j] * xp[j:j + T] for j in range(K))


def delta_rule(q, k, v, beta, alpha):
    """The recurrence itself, token by token: ``q, k (T, H, dk)``,
    ``v (T, H, dv)``, ``beta, alpha (T, H)``; returns ``(o (T, H, dv),
    S_T (H, dk, dv))``."""
    H, dk = q.shape[1:]
    dv = v.shape[2]

    def step(S, t):
        qt, kt, vt, bt, at = t
        S = at[:, None, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=HI))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)
    S, o = lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                    (q, k, v, beta, alpha))
    return o, S


def _gdn(h, p, D, low):
    """The Gated DeltaNet mixer over one sequence ``h (T, d)``."""
    f32 = lambda a: a.astype(jnp.float32)
    T = h.shape[0]
    HL, dk, dv = D["HL"], D["dk"], D["dv"]
    q = jax.nn.silu(_conv(_mm(h, p["w_q"], low), p["conv_q"]))
    k = jax.nn.silu(_conv(_mm(h, p["w_k"], low), p["conv_k"]))
    v = jax.nn.silu(_conv(_mm(h, p["w_v"], low), p["conv_v"]))
    q, k, v = (a.reshape(T, HL, -1) for a in (q, k, v))
    l2 = lambda a: a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)
    q, k = l2(q) * dk ** -0.5, l2(k)
    beta = 2.0 * jax.nn.sigmoid(_mm(h, p["w_b"], low))
    alpha = jnp.exp(-jnp.exp(f32(p["a_log"])) * jax.nn.softplus(
        _mm(h, p["w_a"], low) + f32(p["dt_bias"])))
    o, _ = delta_rule(q, k, v, beta, alpha)
    o = _rms(o, p["g_norm"], D["eps"]).reshape(T, HL * dv)
    return _mm(o * jax.nn.silu(_mm(h, p["w_g"], low)), p["w_o"], low)


def _attention(h, p, D, low):
    """Causal softmax attention of one sequence, a block of queries at a
    time against every key."""
    T = h.shape[0]
    H, dh = D["H"], D["dh"]
    q = _rms(_mm(h, p["w_q"], low), p["q_norm"], D["eps"]).reshape(T, H, dh)
    k = _rms(_mm(h, p["w_k"], low), p["k_norm"], D["eps"]).reshape(T, H, dh)
    v = _mm(h, p["w_v"], low).reshape(T, H, dh)
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * B, B)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(dh)
        rows = i * B + jnp.arange(B)[:, None]
        a = jax.nn.softmax(jnp.where(keys <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, H * dh)
    return _mm(o, p["w_o"], low)


@functools.partial(jax.jit, static_argnames=("kind", "Dt", "low"))
def _layer(x, p, kind: str, Dt: tuple, low: bool):
    """One layer over one sequence ``x (T, d)`` in float32."""
    D = dict(Dt)
    m = p["mixer"]
    out = _gdn(x, m, D, low) if kind == "linear_attention" \
        else _attention(x, m, D, low)
    y = x + _rms(out, p["norm_1"], D["eps"])
    f = p["mlp"]
    ff = _mm(jax.nn.silu(_mm(y, f["w_gate"], low)) * _mm(y, f["w_up"], low),
             f["w_down"], low)
    return y + _rms(ff, p["norm_2"], D["eps"])


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, head, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), head, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    D = dims(config)
    Dt = tuple(sorted(D.items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for kind, p in zip(layer_kinds(config), weights["layers"]):
        x = _layer(x, p, kind, Dt, low)
    return x


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: every mixer is causal."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["head"],
                 config["rms_norm_eps"], low)


def pad_to(tokens: list, multiple: int = QUERY_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _gaps(x, x_low, norm_f, head, nxt, eps: float, low: bool):
    """Per position: how far the reference's logit of the token ``nxt``
    (the one that followed) lies below the reference's best, and the same
    for the token that the control's hidden state ``x_low`` puts first."""
    ref = _head(x, norm_f, head, eps, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    ctl = _head(x_low, norm_f, head, eps, low)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(ctl, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode).  The
    sequence is padded to a multiple of 512 (4,608 at most in the cell),
    so a handful of programs serve every request."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    # the served positions in a fixed-size slab, so that the head's
    # program is one whatever the output's length
    slab = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    at = min(first, len(padded) - slab)
    nxt = jnp.asarray(pad_to(seq[1:])[at:at + slab], jnp.int32)
    x = hidden(config, weights, padded)[at:at + slab]
    x_low = hidden(config, weights, padded, True)[at:at + slab] \
        if control else x
    got, low = _gaps(x, x_low, weights["norm_f"], weights["head"], nxt,
                     config["rms_norm_eps"], control)
    where = slice(first - at, first - at + n)
    out = {"served": np.asarray(got)[where].tolist()}
    if control:
        out["control"] = np.asarray(low)[where].tolist()
    return out


# -- counts for the rooflines ------------------------------------------------
def layer_params(config: dict) -> dict:
    """Parameters of one layer of each kind."""
    D = dims(config)
    d, ff, HL, dk, dv, K = (D[n] for n in ("d", "ff", "HL", "dk", "dv", "K"))
    common = 2 * d + 3 * d * ff
    gdn = 2 * d * HL * dk + 3 * d * HL * dv + 2 * d * HL \
        + K * HL * (2 * dk + dv) + 2 * HL + dv
    full = 4 * d * D["H"] * D["dh"] + 2 * D["H"] * D["dh"]
    return {"linear_attention": common + gdn, "full_attention": common + full}


def param_count(config: dict) -> int:
    D = dims(config)
    per = layer_params(config)
    return 2 * D["V"] * D["d"] + D["d"] + sum(
        per[k] for k in layer_kinds(config))


def param_bytes(config: dict, itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: every layer's
    matrices, the final norm and the untied head.  The embedding table is
    not among them: a step gathers one row a slot."""
    D = dims(config)
    return float(itemsize * (param_count(config) - D["V"] * D["d"]))


def cache_bytes(config: dict) -> dict:
    """Bytes of each kind of state that the layers keep between steps:
    ``paged`` per live position (the key and value rows of every full
    layer, bfloat16, each read by its own layer alone), ``recurrent`` per
    live slot (every linear layer's float32 ``(H, dk, dv)`` state and the
    convolutions' ``K - 1`` bfloat16 rows of ``H (2 dk + dv)``)."""
    D = dims(config)
    kinds = layer_kinds(config)
    HL, dk, dv, K = D["HL"], D["dk"], D["dv"], D["K"]
    return {"paged": float(kinds.count("full_attention") * 2 * D["H"]
                           * D["dh"] * 2),
            "recurrent": float(kinds.count("linear_attention") * (
                4 * HL * dk * dv + 2 * (K - 1) * HL * (2 * dk + dv)))}


def decode_step_bytes(config: dict, live_positions: float,
                      live_slots: float = 0.0) -> float:
    """Bytes one decode step needs to move: the weights once less the
    embedding table, the paged rows of the live positions, and the
    recurrent state of the live slots read and written.  The step is
    bound by bytes (16 rows against 3.7 B weights)."""
    c = cache_bytes(config)
    return param_bytes(config) + live_positions * c["paged"] \
        + 2.0 * live_slots * c["recurrent"]


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight of every layer's matrices at every position; the causal
    half of the scores and of the context in the full layers; the delta
    rule as its recurrence counts them (decay, ``S^T k``, the rank-one
    update, ``S^T q``: 7 a state element a position), whatever form the
    program computes it in; the head at the last position alone."""
    D = dims(config)
    d, ff, HL, dk, dv = (D[n] for n in ("d", "ff", "HL", "dk", "dv"))
    kinds = layer_kinds(config)
    nL, nF = kinds.count("linear_attention"), kinds.count("full_attention")
    Hd = D["H"] * D["dh"]
    matrices = (nL + nF) * 3 * d * ff \
        + nL * (2 * d * HL * dk + 3 * d * HL * dv + 2 * d * HL) \
        + nF * 4 * d * Hd
    return 2.0 * matrices * t + nF * 2.0 * t * t * Hd \
        + nL * 7.0 * HL * dk * dv * t + 2.0 * d * D["V"]
