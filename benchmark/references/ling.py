"""Plain reference for the ``ling`` family: Ling-3.0-flash (inclusionAI,
``model_type`` ``bailing_hybrid``): Kimi-Delta-Attention layers (KDA; Kimi
Linear, arXiv:2510.26692) beside multi-head latent attention (MLA;
DeepSeek-V2, arXiv:2405.04434) and group-routed experts with a correction
bias (DeepSeek-V3's ``noaux_tc``, arXiv:2412.19437), as ONE CHIP'S SHARE
of a deployment: it is told which of the routed experts it holds
(``experts_held``), which slice of the vocabulary (``vocab_size`` rows)
and which published layers (``first_layer`` on), routes over all
``router_width`` experts, and adds only its own experts' part.  The full
causal forward pass in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision, one sequence at a time, no cache, no
batching, one layer at a time; **the delta rule is the token-by-token
recurrence below**, a sequential ``lax.scan`` (so the program's chunked
form in sub-blocks is compared with the definition and not with itself),
attention UNABSORBED over blocks of 256 queries and the held experts
upcast one at a time, so that 12,288 positions fit beside the held
weights.

It imports nothing of the program and takes nothing the program made: the
weights are made here from the seed (bfloat16 leaves, the router's bias
float32) and the family's builder (``configs/ling.py``) hands the same
arrays to the program.

The equations.  ``d`` 2560, ``H`` 32 heads, ``x`` a ``(T, d)`` sequence,
RMSNorm ``x / sqrt(mean(x^2) + 1e-6) * g``.

*Block* (pre-norm): ``y = x + Mixer(RMSNorm_1(x))``, ``out = y +
FFN(RMSNorm_2(y))``; a final RMSNorm; an untied head over the
vocabulary's slice.  Published layer ``i`` is MLA where ``(i + 1) %
layer_group_size == 0``, else KDA; layer ``j`` of the ones held is
published layer ``first_layer + j``, and its FFN is dense where ``j <
first_k_dense_replace``.

*KDA mixer* on ``h``, ``dk = dv = head_dim`` 128, ``K`` 4::

    q = silu(conv_q(h Wq)), k = silu(conv_k(h Wk)), v = silu(conv_v(h Wv))
        (depthwise causal convolutions of K taps, no bias: tap K-1 meets
        the position itself, zeros before the sequence)
    per head: q <- q / sqrt(sum(q^2) + 1e-6) * dk^-1/2,
              k <- k / sqrt(sum(k^2) + 1e-6)
    beta = sigmoid(h Wb)                                        (T, H)
    g = kda_lower_bound * sigmoid(exp(A_log) * (h Wf + dt_bias))
        (T, H, dk): the log decay a CHANNEL, -5 <= g <= 0 (A_log a head,
        dt_bias a channel)
    per head, S in R^{dk x dv}, zero at the sequence's start:
        S' = Diag(exp(g_t)) S_{t-1}
        u_t = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u_t^T
        o_t = S_t^T q_t
    out = (RMSNorm_dv(o) a head * sigmoid(h Wg) a head) Wo      Wg (d, H)

*MLA mixer* on ``h``::

    q = h Wq -> H heads of [q_nope 128 | q_rope 64]     (no q compression)
    [c_kv | k_r] = h W_dkv                               (T, 512 | 64)
    c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)               one k_r for all heads
    q_rope = RoPE(q_rope)
    k_nope = c_kv W_uk, v = c_kv W_uv -> H heads of 128 each
    s = (q_nope . k_nope + q_rope . k_r) / sqrt(192), causal softmax
    o = concat_h(softmax(s) v) W_o

RoPE pairs lane ``2 i`` with lane ``2 i + 1`` of the 64 (``rope_interleave``)
and turns the pair by ``t * theta^(-i / 32)``, ``theta`` 6e6, ``t`` the
position.

*FFN*, dense: ``W_down(silu(x W_gate) * x W_up)``, width 6,144.  *Expert
layer*: ``s = sigmoid(x W_r)`` over all 512; ``c = s + b`` (the correction
bias: in the choice only); the experts lie in 8 groups of 64, a group's
score is the sum of its two largest ``c``, the 4 best groups stay, among
their 256 experts the 8 largest ``c`` are chosen; ``w_e = s_e / (sum of
the 8 s) * 2.5``; ``out = Shared(x) + sum over chosen e in experts_held of
w_e Expert_e(x)``, each expert and the shared one a gated SiLU FFN of
width 768.  What the experts outside ``experts_held`` would add is left
out.

What ``config.json`` does not carry is listed in the configuration's file
under ``assumed``.
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
QUERY_BLOCK = 256
#: lengths are padded to a multiple of this, so a handful of programs
#: serve every request
LENGTH_BLOCK = 512
#: folded into the seed's key for the tokens the routers are balanced on
#: (0 draws the ends, ``first_layer + j + 1`` layer ``j``)
BALANCE_KEY = 1 << 20


def dims(config: dict) -> dict:
    lo, hi = config["experts_held"]
    if hi - lo != config["num_experts"]:
        raise ValueError("experts_held does not name num_experts experts")
    if config["router_width"] % config["n_group"]:
        raise ValueError("the router's outputs do not divide into n_group")
    return {"d": config["hidden_size"], "H": config["num_attention_heads"],
            "dh": config["head_dim"], "K": config["short_conv_kernel_size"],
            "bound": float(config["kda_lower_bound"]),
            "rkv": config["kv_lora_rank"],
            "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "ff": config["intermediate_size"],
            "fe": config["moe_intermediate_size"],
            "E": config["router_width"], "lo": lo, "n": hi - lo,
            "k": config["num_experts_per_tok"], "G": config["n_group"],
            "Gk": config["topk_group"],
            "shared": config["num_shared_experts"],
            "scale": config["routed_scaling_factor"],
            "L": config["num_hidden_layers"],
            "dense": config["first_k_dense_replace"],
            "first": config["first_layer"],
            "period": config["layer_group_size"],
            "V": config["vocab_size"], "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"])}


def layer_kinds(config: dict) -> list:
    """``"kda"`` or ``"mla"`` for each layer held, by its published
    index."""
    first, period = config["first_layer"], config["layer_group_size"]
    return ["mla" if (first + j + 1) % period == 0 else "kda"
            for j in range(config["num_hidden_layers"])]


def published(config: dict) -> dict:
    """The configuration with the counts this chip's share cut put back
    as the source has them (``config["published"]``): all layers from the
    first, all routed experts, the whole vocabulary."""
    whole = dict(config, **config["published"])
    whole["experts_held"] = [0, whole["num_experts"]]
    whole["first_layer"] = 0
    return whole


def experts_held(config: dict) -> int:
    """Routed experts this chip holds in each expert layer."""
    return config["num_experts"]


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


# -- weights ---------------------------------------------------------------
def _normal(key, shape):
    return (STD * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("kind", "dense", "Dt"))
def _make_layer(key, kind: str, dense: bool, Dt: tuple):
    D = dict(Dt)
    d, H, dh, fe, K = D["d"], D["H"], D["dh"], D["fe"], D["K"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 32))
    normal = lambda *shape: _normal(next(keys), shape)
    ones = lambda n: jnp.ones((n,), bf)

    def conv():            # as torch draws a depthwise Conv1d: +-K^-0.5
        return jax.random.uniform(next(keys), (K, H * dh), jnp.float32,
                                  -K ** -0.5, K ** -0.5).astype(bf)

    def uniform(shape, lo, hi, dtype=bf):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi
                                  ).astype(dtype)
    p = {"norm_1": ones(d), "norm_2": ones(d)}
    if kind == "kda":
        # A_log and dt_bias drawn so that the channels' decays a step
        # spread from ~0.05 to ~0.998 (assumed.kda_gate_draw)
        p["kda"] = {
            "w_q": normal(d, H * dh), "w_k": normal(d, H * dh),
            "w_v": normal(d, H * dh), "w_f": normal(d, H * dh),
            "w_b": normal(d, H), "w_g": normal(d, H),
            "w_o": normal(H * dh, d),
            "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
            "a_log": jnp.log(uniform((H,), 0.5, 2.0, jnp.float32)).astype(bf),
            "dt_bias": uniform((H * dh,), -6.0, 0.0),
            "o_norm": ones(dh)}
    elif kind == "mla":
        p["mla"] = {
            "w_q": normal(d, H * (D["nope"] + D["rope"])),
            "w_dkv": normal(d, D["rkv"] + D["rope"]),
            "kv_norm": ones(D["rkv"]),
            # a head's own matrix, heads leading: W_uk (512, 128) and W_uv
            # TRANSPOSED (128, 512)
            "w_uk": normal(H, D["rkv"], D["nope"]),
            "w_uv_t": normal(H, D["dv"], D["rkv"]),
            "w_o": normal(H * D["dv"], d)}
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if dense:
        p["mlp"] = {"w_gate": normal(d, D["ff"]), "w_up": normal(d, D["ff"]),
                    "w_down": normal(D["ff"], d)}
        return p
    # an expert's weights depend on the seed and on its index among ALL
    # the routed experts, so the four shares of a layer together hold the
    # experts the uncut layer holds
    base = next(keys)

    def expert(e):
        kg, ku, kd = jax.random.split(jax.random.fold_in(base, e), 3)
        return _normal(kg, (d, fe)), _normal(ku, (d, fe)), \
            _normal(kd, (fe, d))
    eg, eu, ed = jax.vmap(expert)(D["lo"] + jnp.arange(D["n"]))
    p["moe"] = {"w_router": normal(d, D["E"]),
                # the correction bias, NOT zero, and small as one trained
                # to BALANCE the load is: +-0.01 beside the 0.005 between
                # a token's eighth and ninth largest score, so it changes
                # a choice of every other token and lets no expert win
                # for all of them (assumed.router); ``_balanced`` then
                # trains it where the configuration says so
                "bias": uniform((D["E"],), -0.01, 0.01, jnp.float32),
                "shared": {"w_gate": normal(d, D["shared"] * fe),
                           "w_up": normal(d, D["shared"] * fe),
                           "w_down": normal(D["shared"] * fe, d)},
                "experts": {"w_gate": eg, "w_up": eu, "w_down": ed}}
    return p


@functools.partial(jax.jit, static_argnames=("V", "d"))
def _make_ends(key, V: int, d: int):
    ke, kh = jax.random.split(key)
    return _normal(ke, (V, d)), _normal(kh, (d, V)), \
        jnp.ones((d,), jnp.bfloat16)


def make_weights(config: dict, key):
    """Seeded weights, made on the device, one small jitted program per
    kind of layer; of the routed experts only those in ``experts_held``.
    A layer's weights depend on the seed and on its PUBLISHED index.
    Where the configuration states ``router_balance``, the expert layers'
    biases are then balanced as a trained router's are (``_balanced``)."""
    D = dims(config)
    Dt = tuple(sorted(D.items()))
    emb, head, norm_f = _make_ends(jax.random.fold_in(key, 0), D["V"], D["d"])
    layers = [_make_layer(jax.random.fold_in(key, D["first"] + j + 1), kind,
                          j < D["dense"], Dt)
              for j, kind in enumerate(layer_kinds(config))]
    if config.get("router_balance"):
        layers = _balanced(layers, emb, key, Dt, config["router_balance"])
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


def rope(x, theta: float):
    """``x (T, ..., D)`` with position ``t`` on the leading axis: lane
    ``2 i`` and lane ``2 i + 1`` turned by ``t * theta^(-2 i / D)``."""
    T, D = x.shape[0], x.shape[-1]
    half = D // 2
    inv = jnp.asarray(theta ** (-np.arange(half) / half), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32).reshape(
        (T,) + (1,) * (x.ndim - 1)) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1
                     ).reshape(x.shape)


def _gated(x, f, low):
    return _mm(jax.nn.silu(_mm(x, f["w_gate"], low)) * _mm(x, f["w_up"], low),
               f["w_down"], low)


def _conv(x, w):
    """Depthwise causal convolution of ``x (T, c)`` with ``w (K, c)``."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), jnp.float32), x])
    w = w.astype(jnp.float32)
    return sum(w[j] * xp[j:j + T] for j in range(K))


def delta_rule(q, k, v, beta, g):
    """The recurrence itself, token by token, the decay a channel: ``q, k
    (T, H, dk)``, ``v (T, H, dv)``, ``beta (T, H)``, ``g (T, H, dk)`` the
    log decay; returns ``(o (T, H, dv), S_T (H, dk, dv))``."""
    H, dk = q.shape[1:]
    dv = v.shape[2]

    def step(S, t):
        qt, kt, vt, bt, gt = t
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt, precision=HI))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)
    S, o = lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                    (q, k, v, beta, g))
    return o, S


def kda_gate(h, p, D, low=False):
    """``g (T, H, dk)``: the log decay a channel behind its safe gate."""
    f32 = lambda a: a.astype(jnp.float32)
    z = (_mm(h, p["w_f"], low) + f32(p["dt_bias"])).reshape(
        h.shape[0], D["H"], D["dh"])
    return D["bound"] * jax.nn.sigmoid(
        jnp.exp(f32(p["a_log"]))[None, :, None] * z)


def _kda(h, p, D, low):
    """The KDA mixer over one sequence ``h (T, d)``."""
    T = h.shape[0]
    H, dh = D["H"], D["dh"]
    q = jax.nn.silu(_conv(_mm(h, p["w_q"], low), p["conv_q"]))
    k = jax.nn.silu(_conv(_mm(h, p["w_k"], low), p["conv_k"]))
    v = jax.nn.silu(_conv(_mm(h, p["w_v"], low), p["conv_v"]))
    q, k, v = (a.reshape(T, H, dh) for a in (q, k, v))
    l2 = lambda a: a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)
    q, k = l2(q) * dh ** -0.5, l2(k)
    beta = jax.nn.sigmoid(_mm(h, p["w_b"], low))
    o, _ = delta_rule(q, k, v, beta, kda_gate(h, p, D, low))
    o = _rms(o, p["o_norm"], D["eps"]) \
        * jax.nn.sigmoid(_mm(h, p["w_g"], low))[:, :, None]
    return _mm(o.reshape(T, H * dh), p["w_o"], low)


def _mla(h, p, D, low):
    """Latent attention of one sequence as written: every position's keys
    and values formed, a block of queries at a time against every key."""
    T = h.shape[0]
    H, nope, rp, dv = D["H"], D["nope"], D["rope"], D["dv"]
    q = _mm(h, p["w_q"], low).reshape(T, H, nope + rp)
    qn, qr = q[..., :nope], rope(q[..., nope:], D["theta"])
    ckr = _mm(h, p["w_dkv"], low)
    ckv = _rms(ckr[:, :D["rkv"]], p["kv_norm"], D["eps"])
    kr = rope(ckr[:, D["rkv"]:], D["theta"])                     # (T, rp)
    # (rank, H x width) matrices out of the heads' own
    a_head = lambda w: _mm(ckv, w.reshape(D["rkv"], -1), low
                           ).reshape(T, H, -1)
    kn = a_head(p["w_uk"].transpose(1, 0, 2))
    v = a_head(p["w_uv_t"].transpose(2, 0, 1))
    B = min(T, QUERY_BLOCK)
    if T % B:
        raise ValueError(f"{T} positions are no multiple of {B}")
    keys = jnp.arange(T)[None, :]

    def block(i):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * B, B)
        s = (jnp.einsum("qhd,khd->hqk", cut(qn), kn, precision=HI)
             + jnp.einsum("qhd,kd->hqk", cut(qr), kr, precision=HI)) \
            / math.sqrt(nope + rp)
        rows = i * B + jnp.arange(B)[:, None]
        a = jax.nn.softmax(jnp.where(keys <= rows, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v, precision=HI)
    o = lax.map(block, jnp.arange(T // B)).reshape(T, H * dv)
    return _mm(o, p["w_o"], low)


def _choose(c, D):
    """The ``k`` experts a token chooses by ``c (T, E)``: the ``Gk`` groups
    whose two largest ``c`` sum highest stay, then the largest ``c`` among
    their experts."""
    T = c.shape[0]
    per = D["E"] // D["G"]
    group = jnp.sum(lax.top_k(c.reshape(T, D["G"], per), 2)[0], axis=-1)
    _, best = lax.top_k(group, D["Gk"])                           # (T, Gk)
    stays = jnp.zeros((T, D["G"]), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    return lax.top_k(jnp.where(jnp.repeat(stays, per, axis=1), c,
                               -jnp.inf), D["k"])[1]


def route(x, m, D, low=False):
    """``(chosen experts (T, k), their weights (T, k))`` over all the
    router's outputs: groups first, the bias in the choice and not in the
    weight."""
    s = jax.nn.sigmoid(_mm(x, m["w_router"], low))                # (T, E)
    idx = _choose(s + m["bias"].astype(jnp.float32), D)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * D["scale"]


def routed_part(x, m, D, low=False):
    """What the held experts add for ``x (T, d)``: every held expert over
    every token, weighted by the token's weight for it (zero where the
    token did not choose it), one expert at a time."""
    idx, w = route(x, m, D, low)

    def one(out, ew):
        e, wg, wu, wd = ew
        c = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)        # (T,)
        y = _gated(x, {"w_gate": wg, "w_up": wu, "w_down": wd}, low)
        return out + c[:, None] * y, None
    ex = m["experts"]
    out, _ = lax.scan(one, jnp.zeros_like(x),
                      (D["lo"] + jnp.arange(D["n"]), ex["w_gate"],
                       ex["w_up"], ex["w_down"]))
    return out


def expert_layer(x, m, D, low=False):
    """``Shared(x)`` plus the held experts' part."""
    return _gated(x, m["shared"], low) + routed_part(x, m, D, low)


@functools.partial(jax.jit, static_argnames=("Dt", "low"))
def _mixed(x, p, Dt: tuple, low: bool):
    """``y = x + Mixer(RMSNorm_1(x))`` and the FFN's input
    ``RMSNorm_2(y)``."""
    D = dict(Dt)
    h = _rms(x, p["norm_1"], D["eps"])
    y = x + (_kda(h, p["kda"], D, low) if "kda" in p
             else _mla(h, p["mla"], D, low))
    return y, _rms(y, p["norm_2"], D["eps"])


@functools.partial(jax.jit, static_argnames=("Dt", "low"))
def _layer(x, p, Dt: tuple, low: bool):
    """One layer over one sequence ``x (T, d)`` in float32."""
    y, h = _mixed(x, p, Dt, low)
    return y + (_gated(h, p["mlp"], low) if "mlp" in p
                else expert_layer(h, p["moe"], dict(Dt), low))


@functools.partial(jax.jit, static_argnames=("Dt", "steps"))
def _balance(h, m, Dt: tuple, steps: int, speed: float):
    """The correction bias after ``steps`` passes of ``noaux_tc``'s own
    rule (DeepSeek-V3, arXiv:2412.19437, 2.1.2) over the tokens ``h (T,
    d)``, from the bias as drawn: after a pass an expert chosen more often
    than the mean loses ``speed``, one chosen less often gains it."""
    D = dict(Dt)
    s = jax.nn.sigmoid(_mm(h, m["w_router"], False))
    mean = h.shape[0] * D["k"] / D["E"]

    def one(_, b):
        load = jnp.zeros((D["E"],), jnp.float32).at[
            _choose(s + b, D).reshape(-1)].add(1.0)
        return b + speed * jnp.sign(mean - load)
    return lax.fori_loop(0, steps, one, m["bias"].astype(jnp.float32))


def _balanced(layers: list, emb, key, Dt: tuple, bal: dict) -> list:
    """The layers with every expert layer's bias balanced over
    ``bal["tokens"]`` seeded tokens as ONE sequence, layer by layer: each
    on what the layers before it, already balanced, hand it."""
    toks = jax.random.randint(jax.random.fold_in(key, BALANCE_KEY),
                              (bal["tokens"],), 0, emb.shape[0])
    x = emb[toks].astype(jnp.float32)
    out = []
    for p in layers:
        if "moe" in p:
            _, h = _mixed(x, p, Dt, False)
            p = dict(p, moe=dict(p["moe"], bias=_balance(
                h, p["moe"], Dt, bal["steps"], bal["speed"])))
        x = _layer(x, p, Dt, False)
        out.append(p)
    return out


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, norm_f, head, eps: float, low: bool):
    return _mm(_rms(x, norm_f, eps), head, low)


def hidden(config: dict, weights, tokens, low: bool = False):
    """The last layer's output ``(len(tokens), d)`` for one sequence of
    token ids, one layer at a time."""
    Dt = tuple(sorted(dims(config).items()))
    x = weights["emb"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for p in weights["layers"]:
        x = _layer(x, p, Dt, low)
    return x


def pad_to(tokens: list, multiple: int = LENGTH_BLOCK) -> list:
    return list(tokens) + [0] * (-len(tokens) % multiple)


def logits(config: dict, weights, tokens, first: int = 0,
           low: bool = False):
    """Logits ``(len(tokens) - first, vocab)`` at positions ``first..`` of
    one sequence.  Padding on the right cannot reach a position before
    it: every mixer is causal and everything else acts a position."""
    padded = pad_to(tokens) if len(tokens) > QUERY_BLOCK else list(tokens)
    x = hidden(config, weights, padded, low)[first:len(tokens)]
    return _head(x, weights["norm_f"], weights["head"],
                 config["rms_norm_eps"], low)


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
                control: bool = False, dense_control: bool = False) -> dict:
    """For one finished request: at every position that produced a served
    token, how far the served token's reference logit lies below the
    reference's best.  With ``control``, also the same gap for the token
    that the float8 computation puts first at that position (teacher
    forced on the same prompt and tokens; it need not decode).  The
    sequence is padded to a multiple of 512 (12,288 at most in the cell).
    ``dense_control`` is the cell's driver asking for a family's second
    control: this family has none, and the keyword changes nothing."""
    seq = list(prompt) + list(served)
    first = len(prompt) - 1
    n = len(served)
    padded = pad_to(seq[:-1])
    slab = -(-n // LENGTH_BLOCK) * LENGTH_BLOCK
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
    """Parameters of a layer by part: ``kda`` and ``mla`` (a mixer's
    matrices with its small vectors), ``norms`` (the block's two),
    ``dense`` (the dense FFN), ``shared`` + ``router`` (what every token
    of an expert layer reads; the router with its bias) and ``expert``
    (ONE routed expert)."""
    D = dims(config)
    d, H, dh, K = D["d"], D["H"], D["dh"], D["K"]
    kda = 5 * d * H * dh + 2 * d * H + 3 * K * H * dh + H + H * dh + dh
    mla = d * H * (D["nope"] + D["rope"]) + d * (D["rkv"] + D["rope"]) \
        + D["rkv"] + D["rkv"] * H * (D["nope"] + D["dv"]) + H * D["dv"] * d
    return {"kda": kda, "mla": mla, "norms": 2 * d, "dense": 3 * d * D["ff"],
            "shared": 3 * d * D["shared"] * D["fe"],
            "router": d * D["E"] + D["E"], "expert": 3 * d * D["fe"]}


def param_count(config: dict) -> int:
    """Parameters held: every layer with the routed experts in
    ``experts_held``, the embedding and the head over ``vocab_size`` rows,
    the final norm."""
    D = dims(config)
    per = layer_params(config)
    mixers = sum(per[k] + per["norms"] for k in layer_kinds(config))
    return 2 * D["V"] * D["d"] + D["d"] + mixers + D["dense"] * per["dense"] \
        + (D["L"] - D["dense"]) * (per["shared"] + per["router"]
                                   + D["n"] * per["expert"])


def param_bytes(config: dict, experts_hit: float = 0.0,
                itemsize: int = 2) -> float:
    """Bytes of the weights one decode step has to read: everything
    outside the routed experts (the embedding table left out: a step
    gathers one row a slot) and ``experts_hit`` routed experts, summed
    over the layers."""
    D = dims(config)
    per = layer_params(config)
    held = (D["L"] - D["dense"]) * D["n"] * per["expert"]
    return float(itemsize * (param_count(config) - D["V"] * D["d"] - held
                             + experts_hit * per["expert"]))


def cache_bytes(config: dict) -> dict:
    """Bytes of each kind of state the layers keep between steps:
    ``paged`` per live position (the latent row ``[c_kv | k_r]`` of every
    MLA layer in bfloat16, the 576 lanes that mean something; the pool
    stores 640), ``recurrent`` per live slot (every KDA layer's float32
    ``(H, dk, dv)`` state and the three convolutions' ``K - 1`` bfloat16
    rows of ``H dk`` each)."""
    D = dims(config)
    kinds = layer_kinds(config)
    H, dh, K = D["H"], D["dh"], D["K"]
    return {"paged": float(kinds.count("mla") * 2 * (D["rkv"] + D["rope"])),
            "recurrent": float(kinds.count("kda") * (
                4 * H * dh * dh + 2 * (K - 1) * 3 * H * dh))}


def kda_state_bytes(config: dict, live_slots: float) -> float:
    """Bytes the KDA layers' state costs one decode step: every live
    slot's delta states and convolution windows read once and written
    once."""
    return 2.0 * live_slots * cache_bytes(config)["recurrent"]


def decode_step_bytes(config: dict, live_positions: float,
                      experts_hit: float = 0.0,
                      live_slots: float = 0.0) -> float:
    """Bytes one decode step needs to move: the weights outside the
    routed experts, ``experts_hit`` routed experts (summed over layers:
    the counter's, not all that are held), the live latent rows, and the
    live slots' recurrent state read and written.  With one argument it
    is what EVERY step reads whatever the router says and however many
    slots are live — a floor under the step's bytes, which the
    benchmark's list-less ``decode_roofline_pct.batch`` reads and so
    never over-counts."""
    return param_bytes(config, experts_hit) \
        + live_positions * cache_bytes(config)["paged"] \
        + kda_state_bytes(config, live_slots)


def prefill_flops(config: dict, t: int) -> float:
    """Operations a prefill of ``t`` (padded) positions requires: two for
    each weight outside the routed experts at every position (the head at
    the last alone); the routed experts for the EXPECTED pairs a token a
    layer, ``k * held / router_width`` (two at 128 of 512 and 8 a token),
    whatever form the program computes them in; the causal half of the
    MLA layers' unabsorbed scores (192 lanes a head) and context (128);
    the delta rule as its recurrence counts it (decay, ``S^T k``, the
    rank-one update, ``S^T q``: 7 a state element a position), whatever
    form the program computes it in."""
    D = dims(config)
    per = layer_params(config)
    kinds = layer_kinds(config)
    nK, nM = kinds.count("kda"), kinds.count("mla")
    nE = D["L"] - D["dense"]
    H, dh = D["H"], D["dh"]
    matrices = nK * (5 * D["d"] * H * dh + 2 * D["d"] * H) \
        + nM * (per["mla"] - D["rkv"]) + D["dense"] * per["dense"] \
        + nE * (per["shared"] + D["d"] * D["E"]
                + D["k"] * D["n"] / D["E"] * per["expert"])
    attention = nM * float(t) * t * H * (D["nope"] + D["rope"] + D["dv"])
    return 2.0 * matrices * t + attention + nK * 7.0 * H * dh * dh * t \
        + 2.0 * D["d"] * D["V"]
