"""Plain reference for the ``phi4flash`` family: Phi-4-mini-flash-reasoning,
the decoder-hybrid-decoder "SambaY" of arXiv:2507.06607 with the
differential attention of arXiv:2410.05258.  The full causal forward pass
in straightforward ``jax.numpy`` float32 at ``highest`` matmul precision,
one sequence at a time, no cache, no batching, one layer at a time so that
2,560 positions fit beside the weights; the state-space recurrence is a
sequential ``lax.scan``.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves, as they were
released) and the family's builder (``configs/phi4flash.py``) hands the
same arrays to the program.

The layers, ``i = 0..L-1`` with ``half = L // 2`` (``L = 32``):
Mamba at even ``i <= half`` (layer ``half`` also hands its scan output
``m = y``, after the ``D`` skip and before the ``z`` gate, to the GMUs),
window attention at odd ``i < half``, full attention at ``half + 1``
(its keys and values are the ones every cross layer reads), GMU at even
``i > half``, cross attention at odd ``i > half + 1``.  Every layer is
``x += mixer(LN1(x)); x += W_down(silu(W_gate u) * (W_up u)), u = LN2(x)``
with LayerNorm (gain and bias, eps 1e-5), no bias in any matrix and no
positional encoding anywhere; the logits are ``LN_f(x) E^T``.

What the released ``config.json`` does not give is listed in the
configuration file under ``assumed``: the state size 16, the convolution
width 4, the expansion 2, the ``dt`` rank ``d / 16``, the differential
form (adjacent query heads pair up, adjacent KV heads pair up, query pair
``p`` reads KV pair ``p // 2``, sub-LayerNorm = RMSNorm over the pair's
128 channels with eps 1e-5), the window's edge (position ``t`` sees keys
``t-W+1..t``), and the initialisation of the seeded weights.
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


def dims(config: dict) -> dict:
    d, H = config["hidden_size"], config["num_attention_heads"]
    d_in = config["mamba_expand"] * d
    return {"d": d, "H": H, "KV": config["num_key_value_heads"],
            "dh": d // H, "ff": config["intermediate_size"],
            "W": config["sliding_window"], "d_in": d_in,
            "N": config["mamba_d_state"], "K": config["mamba_d_conv"],
            "R": config["mamba_dt_rank"], "L": config["num_hidden_layers"],
            "V": config["vocab_size"], "eps": config["layer_norm_eps"]}


def layer_kinds(config: dict) -> list:
    L = config["num_hidden_layers"]
    half = L // 2
    period = config["mb_per_layer"]
    kinds = []
    for i in range(L):
        if i % period == 0:
            kinds.append("mamba" if i <= half else "gmu")
        elif i < half:
            kinds.append("window")
        else:
            kinds.append("full" if i == half + 1 else "cross")
    return kinds


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# -- weights ---------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "dm"))
def _make_layer(key, kind: str, dm: tuple):
    d, H, KV, dh, ff, d_in, N, K, R = dm
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std=STD):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(bf)

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(bf)

    ln = lambda: {"g": jnp.ones((d,), bf), "b": jnp.zeros((d,), bf)}
    p = {"ln_1": ln(), "ln_2": ln(),
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
            "d_skip": jnp.ones((d_in,), bf),
            "w_out": normal((d_in, d))}
    elif kind == "gmu":
        p["mixer"] = {"w_1": normal((d, d_in)), "w_2": normal((d_in, d))}
    else:
        m = {"w_q": normal((d, H * dh)), "w_o": normal((H * dh, d)),
             "subln_g": jnp.ones((2 * dh,), bf)}
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            m[name] = normal((dh,), 0.1)
        if kind != "cross":
            m["w_k"] = normal((d, KV * dh))
            m["w_v"] = normal((d, KV * dh))
        p["mixer"] = m
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    bf = jnp.bfloat16
    emb = (STD * jax.random.normal(key, (V, d), jnp.float32)).astype(bf)
    return emb, {"g": jnp.ones((d,), bf), "b": jnp.zeros((d,), bf)}


def make_weights(config: dict, key):
    """Seeded bfloat16 weights, made on the device, one small jitted
    program per kind of layer."""
    D = dims(config)
    dm = tuple(D[n] for n in ("d", "H", "KV", "dh", "ff", "d_in", "N", "K",
                              "R"))
    emb, ln_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, i + 1), kind, dm)
              for i, kind in enumerate(layer_kinds(config))]
    return {"emb": emb, "ln_f": ln_f, "layers": layers}


# -- the forward pass -------------------------------------------------------
def _mm(a, w, low: bool):
    """A matmul with a weight, float32 at ``highest``.  ``low`` is the
    control: its input and its weight are rounded to float8 (e4m3)."""
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if low:
        a, w = a.astype(F8).astype(jnp.float32), w.astype(F8).astype(
            jnp.float32)
    return jnp.matmul(a, w, precision=HI)


def _ln(x, p, eps):
    g, b = p["g"].astype(jnp.float32), p["b"].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def _mamba(h, p, D, low):
    """``(out, y)`` over one sequence ``h (T, d)``."""
    f32 = lambda a: a.astype(jnp.float32)
    T = h.shape[0]
    d_in, N, K, R = D["d_in"], D["N"], D["K"], D["R"]
    xz = _mm(h, p["w_in"], low)
    u, z = xz[:, :d_in], xz[:, d_in:]
    up = jnp.concatenate([jnp.zeros((K - 1, d_in), jnp.float32), u])
    conv = sum(f32(p["conv_w"])[k] * up[k:k + T] for k in range(K))
    u = jax.nn.silu(conv + f32(p["conv_b"]))
    dbc = _mm(u, p["w_x"], low)
    delta, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    Delta = jax.nn.softplus(_mm(delta, p["w_dt"], low) + f32(p["b_dt"]))
    A = -jnp.exp(f32(p["a_log"]))                           # (d_in, N)

    def step(s, t):
        Dt, ut, Bt, Ct = t
        s = jnp.exp(Dt[:, None] * A) * s + (Dt * ut)[:, None] * Bt[None, :]
        return s, jnp.sum(s * Ct[None, :], axis=-1)
    _, y = lax.scan(step, jnp.zeros((d_in, N), jnp.float32),
                    (Delta, u, B, C))
    y = y + f32(p["d_skip"]) * u
    return _mm(y * jax.nn.silu(z), p["w_out"], low), y


def _diff_attention(q, k, v, p, li, window, D):
    """Differential attention of one sequence: ``q (T, H, dh)`` against
    ``k, v (T, KV, dh)``, causal, within ``window`` keys if given."""
    f32 = lambda a: a.astype(jnp.float32)
    T, H, dh = q.shape
    KV = k.shape[1]
    P, G = H // 2, KV // 2
    q = q.reshape(T, P, 2, dh)
    k = jnp.repeat(k.reshape(T, G, 2, dh), P // G, axis=1)   # (T, P, 2, dh)
    v = jnp.repeat(v.reshape(T, G, 2 * dh), P // G, axis=1)  # [v_g1 ; v_g2]
    s = jnp.einsum("tpjd,spjd->pjts", q, k, precision=HI) / math.sqrt(dh)
    t_, s_ = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = s_ <= t_
    if window is not None:
        mask = mask & (s_ > t_ - window)
    a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    lam = jnp.exp(jnp.sum(f32(p["lambda_q1"]) * f32(p["lambda_k1"]))) \
        - jnp.exp(jnp.sum(f32(p["lambda_q2"]) * f32(p["lambda_k2"]))) + li
    o = jnp.einsum("pts,spc->tpc", a[:, 0] - lam * a[:, 1], v, precision=HI)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5) \
        * f32(p["subln_g"]) * (1.0 - li)
    return o.reshape(T, P * 2 * dh)


@functools.partial(jax.jit, static_argnames=("kind", "Dt", "low"))
def _layer(x, p, mem, kv, li, kind: str, Dt: tuple, low: bool):
    """One layer over one sequence ``x (T, d)`` in float32; returns
    ``(x, mem, kv)``: a Mamba layer's ``y`` (the caller keeps the memory
    layer's), the full layer's keys and values, else what came in.
    ``li`` is the layer's ``lambda_init`` (one program per kind)."""
    D = dict(Dt)
    T = x.shape[0]
    H, KV, dh = D["H"], D["KV"], D["dh"]
    h = _ln(x, p["ln_1"], D["eps"])
    m = p["mixer"]
    if kind == "mamba":
        out, mem = _mamba(h, m, D, low)
    elif kind == "gmu":
        out = _mm(jax.nn.silu(_mm(h, m["w_1"], low)) * mem, m["w_2"], low)
    else:
        q = _mm(h, m["w_q"], low).reshape(T, H, dh)
        if kind != "cross":
            k = _mm(h, m["w_k"], low).reshape(T, KV, dh)
            v = _mm(h, m["w_v"], low).reshape(T, KV, dh)
            if kind == "full":
                kv = (k, v)
        else:
            k, v = kv
        o = _diff_attention(q, k, v, m, li,
                            D["W"] if kind == "window" else None, D)
        out = _mm(o, m["w_o"], low)
    x = x + out
    u = _ln(x, p["ln_2"], D["eps"])
    f = p["mlp"]
    x = x + _mm(jax.nn.silu(_mm(u, f["w_gate"], low)) * _mm(u, f["w_up"], low),
                f["w_down"], low)
    return x, mem, kv


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, ln_f, emb, eps: float, low: bool):
    return _mm(_ln(x, ln_f, eps), emb.T, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    D = dims(config)
    Dt = tuple(sorted(D.items()))
    T = len(tokens)
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    mem = jnp.zeros((T, D["d_in"]), jnp.float32)
    kv = (jnp.zeros((T, D["KV"], D["dh"]), jnp.float32),) * 2
    for i, (kind, p) in enumerate(zip(layer_kinds(config),
                                      weights["layers"])):
        x, y, kv = _layer(x, p, mem, kv, jnp.float32(lambda_init(i)), kind,
                          Dt, low)
        if kind != "mamba" or i == D["L"] // 2:
            mem = y
    return x


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: every mixer is causal."""
    return _head(hidden(config, weights, tokens, low)[first:],
                 weights["ln_f"], weights["emb"], config["layer_norm_eps"],
                 low)


def pad_to(tokens: list, multiple: int = 512) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _gaps(x, x_low, ln_f, emb, nxt, eps: float, low: bool):
    """Per position: how far the reference's logit of the token ``nxt``
    (the one that followed) lies below the reference's best, and the same
    for the token that the control's hidden state ``x_low`` puts first."""
    ref = _head(x, ln_f, emb, eps, False)
    best = jnp.max(ref, axis=-1)
    rows = jnp.arange(ref.shape[0])
    ctl = _head(x_low, ln_f, emb, eps, low)
    return (best - ref[rows, nxt],
            best - ref[rows, jnp.argmax(ctl, axis=-1)])


def served_gaps(config: dict, weights, prompt: list, served: list,
                control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode)."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    nxt = jnp.asarray(pad_to(seq[1:])[first:first + n], jnp.int32)
    x = hidden(config, weights, padded)[first:first + n]
    x_low = hidden(config, weights, padded, True)[first:first + n] \
        if control else x
    got, low = _gaps(x, x_low, weights["ln_f"], weights["emb"], nxt,
                     config["layer_norm_eps"], control)
    out = {"served": np.asarray(got).tolist()}
    if control:
        out["control"] = np.asarray(low).tolist()
    return out


# -- counts for the roofline -------------------------------------------------
def param_count(config: dict) -> int:
    D = dims(config)
    d, ff, d_in, N, K, R = (D[n] for n in ("d", "ff", "d_in", "N", "K", "R"))
    Hd, KVd, dh = D["H"] * D["dh"], D["KV"] * D["dh"], D["dh"]
    common = 4 * d + 3 * d * ff
    attn = 2 * d * Hd + 4 * dh + 2 * dh
    mixer = {"mamba": 2 * d * d_in + (K + 1) * d_in + d_in * (R + 2 * N)
             + (R + 1) * d_in + d_in * N + d_in + d_in * d,
             "gmu": 2 * d * d_in, "cross": attn,
             "window": attn + 2 * d * KVd, "full": attn + 2 * d * KVd}
    return D["V"] * d + 2 * d + sum(common + mixer[k]
                                    for k in layer_kinds(config))


def param_bytes(config: dict, itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: every layer's
    matrices and the tied embedding as the output head."""
    return float(itemsize * param_count(config))


def cache_bytes(config: dict) -> dict:
    """Bytes of each kind of state that the layers keep between steps:
    ``paged`` per live position (the full layer's key and value rows),
    ``ring`` per live ring row (all window layers), ``recurrent`` per
    live slot (all Mamba layers: the float32 state and the convolution's
    ``K - 1`` bfloat16 rows)."""
    D = dims(config)
    kinds = layer_kinds(config)
    row = 2 * D["KV"] * D["dh"] * 2                      # K and V, bfloat16
    return {"paged": float(row * kinds.count("full")),
            "ring": float(row * kinds.count("window")),
            "recurrent": float(kinds.count("mamba") * D["d_in"]
                               * (4 * D["N"] + 2 * (D["K"] - 1))),
            "paged_readers": 1 + kinds.count("cross")}


def decode_step_bytes(config: dict, live_tokens: float,
                      ring_rows: float = 0.0, state_slots: float = 0.0
                      ) -> float:
    """Bytes one decode step needs to move: the weights once, the paged
    rows of the live positions once for each of the layers that read
    them, the live ring rows once, and the recurrent state of the live
    slots read and written.  The step is bound by bytes (32 rows against
    3.85 B weights).  With only ``live_tokens`` given it is the count the
    gpt2 family makes: weights and paged keys and values."""
    c = cache_bytes(config)
    return param_bytes(config) + live_tokens * c["paged"] * c["paged_readers"] \
        + ring_rows * c["ring"] + 2.0 * state_slots * c["recurrent"]
